"""compile_cache_hit_share: of set-up's ``jax.backend_compile`` spans (those
that ended before the window's first call span began) that say whether the
persistent compilation cache was hit or missed, the share that hit. 100 on
a warm run; under 100 on the first run in a fresh checkout. No span that
says: nothing."""
import program_spans


def read(run):
    found = program_spans.setup_spans(run, "jax.backend_compile")
    said = [s.get("cache") for s in found or ()
            if s.get("cache") in ("hit", "miss")]
    if not said:
        return None
    return 100.0 * said.count("hit") / len(said)
