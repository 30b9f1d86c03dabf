"""Analytic FLOP accounting and MFU (model FLOPs utilization).

The reference framework publishes only relative numbers (its
mkdocs/performance.md is a TODO), so fiber_tpu sets the absolute bar
itself: ``mfu`` is analytic model FLOPs per second divided by the
aggregate peak matmul FLOPs of the devices the measurement ran on.

Counting conventions (stated so the numbers are auditable):

- A matmul (m, k) x (k, n) counts ``2*m*k*n`` FLOPs (multiply + add).
- Attention fwd counts the two S x S matmuls (QK^T and P.V); causal
  halves them. Softmax/normalization elementwise work is excluded
  (standard MFU practice — it is not MXU work).
- A training step counts fwd + backward; backward is 2x forward
  (one matmul each for grad-wrt-input and grad-wrt-weight per fwd
  matmul). Optimizer elementwise updates are excluded.
- Policy counters count the policy network only; environment physics
  is a few dozen scalar ops per step (see ``ENV_STEP_FLOPS``) and is
  included in the rollout totals but is negligible for every shipped
  env except the pixel renderer.

Peak figures are bf16 MXU peaks per *jax device* (on v2/v3 a device is
one TensorCore, half a chip; v4 onward a device is one chip). Public
numbers, one table keyed by ``device_kind``. A TPU that is not in the
table is an error, not a default and not an environment variable: add
its row with its source. The CPU has no row and resolves to "no peak"
(``None``) — an honest answer, so CPU runs report ``mfu: null``.
"""

from __future__ import annotations

from typing import Optional, Sequence

#: bf16 peak matmul FLOP/s per jax device, by substring of device_kind
#: (checked in order; first match wins). Sources: published TPU specs.
_PEAK_BY_KIND = (
    ("v6", 918e12),        # Trillium / v6e chip
    ("v5p", 459e12),       # v5p chip
    ("v5 lite", 197e12),   # v5e chip
    ("v5e", 197e12),
    ("v4 lite", 138e12),   # v4i inference chip
    ("v4", 275e12),        # v4 chip (megacore device)
    ("v3", 61.5e12),       # v3 TensorCore (123e12 per 2-core chip)
    ("v2", 22.5e12),       # v2 TensorCore (45e12 per 2-core chip)
)

#: Approximate scalar FLOPs per env.step for the shipped envs (physics
#: only, excluding the policy). PixelChase includes its 24x24 render.
ENV_STEP_FLOPS = {
    "CartPole": 50.0,
    "ParamCartPole": 60.0,
    "Pendulum": 40.0,
    "PixelChase": 3e3,
    "DeceptiveMaze": 60.0,
    "ParamHillWalker": 200.0,
    "ParamBipedWalker": 600.0,
}


def _resolve_peak(device):
    """Single source of truth for peak resolution — both the MFU math
    (device_peak_flops) and the audit fields (peak_report) derive from
    this, so the reported row can never diverge from the peak used.

    Returns ``(kind, peak, row)``: lowercased device_kind (platform as
    fallback), peak FLOP/s, and the human-auditable row string
    ("<table-sub>:<peak>"). A non-TPU device has no peak:
    ``(kind, None, None)``. A TPU whose device_kind matches no row
    raises ``LookupError``."""
    kind = ((getattr(device, "device_kind", "") or "").lower()
            or getattr(device, "platform", ""))
    if "tpu" not in kind and getattr(device, "platform", "") != "tpu":
        return kind, None, None
    for sub, peak in _PEAK_BY_KIND:
        if sub in kind:
            return kind, peak, f"{sub}:{peak:.4g}"
    raise LookupError(
        f"no peak FLOP/s row for TPU device_kind {kind!r}: add it to "
        "_PEAK_BY_KIND in fiber_tpu/utils/flops.py with its source")


def device_peak_flops(device) -> Optional[float]:
    """bf16 peak matmul FLOP/s for one jax device; None for a device
    that is not a TPU (an MFU against a CPU 'peak' would be noise, not
    signal). Raises ``LookupError`` for a TPU with no table row."""
    return _resolve_peak(device)[1]


def peak_report(devices: Sequence) -> dict:
    """Self-validation fields for bench records: the device_kind the
    measurement ran on and which peak-table row it resolved to, so an
    MFU figure is auditable without rerunning."""
    kind, _, row = _resolve_peak(devices[0])
    return {"device_kind": kind, "peak_row": row}


def mfu(flops_per_sec: float, devices: Sequence) -> Optional[float]:
    """``flops_per_sec`` achieved across ``devices``, as a fraction of
    their aggregate bf16 peak. None when any device's peak is unknown."""
    total = 0.0
    for d in devices:
        peak = device_peak_flops(d)
        if not peak:
            return None
        total += peak
    return flops_per_sec / total if total else None


# ---------------------------------------------------------------------------
# Model counters
# ---------------------------------------------------------------------------


def matmul_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def attention_flops(seq: int, heads: int, head_dim: int,
                    causal: bool = True, train: bool = False,
                    window: Optional[int] = None) -> float:
    """QK^T + P.V for one head stack at full sequence length. With a
    causal sliding ``window`` each position attends min(pos+1, window)
    keys instead of pos+1."""
    if window is not None:
        if not causal:
            # Mirrors the kernel contract (window requires causal) —
            # silently returning the causal count would deflate a
            # non-causal figure by ~2x.
            raise ValueError("windowed attention_flops requires causal")
        w = min(window, seq)
        # ramp-up prefix (positions 0..w-1 attend pos+1) + steady state
        kv_total = w * (w + 1) / 2 + (seq - w) * w
        fwd = 2 * 2 * kv_total * head_dim * heads
        return fwd * (3.0 if train else 1.0)
    fwd = 2 * matmul_flops(seq, head_dim, seq) * heads
    if causal:
        fwd /= 2
    return fwd * (3.0 if train else 1.0)


def tinylm_flops_per_step(model, seq: int, train: bool = True) -> float:
    """One TinyLM forward (or train: fwd + 2x bwd) at ``seq`` tokens.
    Counts the per-block qkv/out/mlp matmuls, attention, and the
    unembedding projection; embeddings are lookups (0 matmul FLOPs)."""
    d, h = model.dim, model.mlp_mult * model.dim
    kvh = getattr(model, "kv_heads", model.heads)
    if kvh == model.heads:
        proj = matmul_flops(seq, d, 3 * d)              # fused wqkv
    else:
        kv_dim = kvh * model.head_dim
        proj = (matmul_flops(seq, d, d)                 # wq
                + matmul_flops(seq, d, 2 * kv_dim))     # wkv
    per_block = (
        proj
        + matmul_flops(seq, d, d)       # wo
        + matmul_flops(seq, d, h)       # w1
        + matmul_flops(seq, h, d)       # w2
        + attention_flops(seq, model.heads, model.head_dim, causal=True,
                          window=getattr(model, "window", None))
    )
    fwd = model.layers * per_block + matmul_flops(seq, d, model.vocab)
    return fwd * (3.0 if train else 1.0)


def policy_flops_per_action(policy) -> float:
    """FLOPs for one forward pass of a shipped policy network."""
    name = type(policy).__name__
    if name == "MLPPolicy":
        return sum(matmul_flops(1, a, b)
                   for a, b in zip(policy.sizes[:-1], policy.sizes[1:]))
    if name == "GRUPolicy":
        o, h, a = policy.obs_dim, policy.hidden, policy.act_dim
        # 3 gates: each (obs + hidden) -> hidden, plus the output head.
        return 3 * (matmul_flops(1, o, h) + matmul_flops(1, h, h)) \
            + matmul_flops(1, h, a)
    if name == "ConvPolicy":
        total = 0.0
        h, w, _ = policy.obs_shape
        for kind, shape in policy._specs:
            if kind == "conv":
                kh, kw, in_c, out_c = shape
                h, w = (h + 1) // 2, (w + 1) // 2  # stride-2 output
                total += matmul_flops(h * w, kh * kw * in_c, out_c)
            else:
                total += matmul_flops(1, *shape)
        return total
    raise ValueError(f"no FLOP counter for policy {name!r}")


def rollout_flops_per_eval(policy, env_name: str, steps: int) -> float:
    """One episode: ``steps`` policy actions plus env physics."""
    return steps * (policy_flops_per_action(policy)
                    + ENV_STEP_FLOPS.get(env_name, 0.0))


def es_flops_per_gen(policy, env_name: str, steps: int, pop: int,
                     dim: int) -> float:
    """One ES generation: ``pop`` rollouts plus the update — noise
    draw, perturbation, fitness-weighted gradient combine (a
    (1, pop) x (pop, dim) matmul) and the parameter step."""
    return (pop * rollout_flops_per_eval(policy, env_name, steps)
            + matmul_flops(1, pop, dim) + 4.0 * pop * dim)
