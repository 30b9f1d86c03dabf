"""Runner kind ``lm_loop_train``: the function ``make_train_step`` returns for
a model whose stack of layers runs several times over the same weights
(``fiber_tpu.models.BlockLM(passes=...)``): sandwich norms, an exit gate, the
expected-exit loss over every pass's logits.

The configuration's file holds the published keys (``ouro``'s);
``workmodel_loop.describe`` turns them into plain data, from which the
program's ``Block``s are built here and which the plain reference is handed as
it is. The donating step, the loop with one step in flight, the reference's
three steps and the comparison of losses, gradient and update are
``lm_moe_train``'s and ``lm_train``'s (``Runner``, subclassed here). This
kind adds two numbers, both of the first batch under the initial weights,
where the mean loss moves little whatever is wrong (it is near ``ln vocab``):
``pass_losses``, the per-position cross-entropy of each pass, and ``exit``,
the per-position exit distribution.
"""

from __future__ import annotations

import importlib

import numpy as np

from workmodel_loop import describe

lm_train = importlib.import_module("runners.lm_train")
_leaf_names = lm_train._leaf_names


def make_step(cfg, traffic, devices, rehearsal=False):
    """The program's objects for the cell: (model, optimizer, the function
    ``make_train_step`` returns, where arrays are placed)."""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from fiber_tpu.models import (Block, BlockLM, ExitGate, Rope,
                                  make_train_step)

    if traffic["mesh"] or int(traffic["batch"]):
        raise ValueError("lm_loop_train runs one sequence a step on one chip")
    spec = describe(cfg)
    block = Block(heads=spec["heads"], rope=Rope(base=spec["rope_base"]),
                  ffn="gated", width=spec["width"], post_norm=True)
    mesh = Mesh(np.asarray(devices[:1]), ("pool",))
    recompute = cfg["recompute"]
    model = BlockLM([block] * spec["layers"], vocab=spec["vocab"],
                    dim=spec["dim"], head_dim=spec["head_dim"],
                    kv_heads=spec["kv_heads"], max_seq=int(traffic["seq"]),
                    attention=traffic["attention"], mesh=mesh,
                    interpret=rehearsal, norm_eps=spec["norm_eps"],
                    passes=spec["passes"],
                    exit_gate=ExitGate(beta=spec["beta"]),
                    recompute=recompute["layers"],
                    head_block=recompute["head_block_rows"])
    o = cfg["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"no optimizer {o['name']!r} here")
    opt = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                      eps=o["eps"], weight_decay=o["weight_decay"])
    step = make_train_step(model, opt, donate=True)
    return model, opt, step, NamedSharding(mesh, PartitionSpec())


def aot_lower(cfg, traffic, devices):
    """The cell's program lowered for ``devices`` (described, not
    attached): the train step, from shapes alone."""
    import jax
    import jax.numpy as jnp

    model, opt, step, place = make_step(cfg, traffic, devices)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=place), tree)

    tokens = jax.ShapeDtypeStruct((int(traffic["seq"]),), jnp.int32,
                                  sharding=place)
    return step.lower(placed(params), placed(opt_state), tokens)


class Runner(lm_train.Runner):
    """``lm_train``'s loop (draw, place, step, wait; one step in flight)
    around this kind's model, reference and comparison."""

    def __init__(self, cfg, traffic, key, seed, devices, spans,
                 rehearsal=False):
        # the model has no window (``sliding_window`` is null), whatever
        # the mix says
        super().__init__(cfg, dict(traffic, use_window=False), key, seed,
                         devices, spans, rehearsal=rehearsal)
        self.spec = describe(cfg)

    # -- set-up ----------------------------------------------------------
    def build(self):
        import jax

        self.model, self.opt, self.step, self.place = make_step(
            self.cfg, self.traffic, self.devices, self.rehearsal)
        # weights on the device, in one jitted call from the seed
        self.init = jax.jit(self.model.init, out_shardings=self.place)
        self.params = self.init(self.key)
        self.opt_state = jax.jit(self.opt.init)(self.params)
        self.next_tokens = self._make_batch()

    def checked_steps(self):
        """``lm_train``'s side of the check and, before the first step, the
        first batch's per-pass losses and exit distribution under the
        initial weights."""
        import jax

        ce, p = jax.device_get(jax.jit(self.model.pass_losses)(
            self.params, self.next_tokens))
        super().checked_steps()
        self.program["pass_losses"] = np.asarray(ce, np.float64)
        self.program["exit"] = np.asarray(p, np.float64)

    def free(self):
        super().free()
        self.model = None

    # -- the check ---------------------------------------------------------
    def reference(self, dtype=None, faults=(), skip_update=False):
        """The plain reference over the checked steps, on one device, from
        the same seed and tokens, and its per-pass losses and exit
        distribution of the first batch under the initial weights.
        ``dtype`` is the control's; ``faults`` (names the reference's head
        lists) and ``skip_update`` (no update is kept: every checked step
        starts from the initial state, and ``update`` reads 1) are the
        planted faults'; the benchmark's own runs pass none of them."""
        import jax
        import jax.numpy as jnp

        ref = importlib.import_module(self.cfg["reference"])

        o = self.cfg["optimizer"]
        row_block = self.traffic.get("reference_row_block")
        with jax.default_device(self.devices[0]):
            params = ref.init_params(self.key, self.spec)
            if dtype is not None:
                params = ref.cast(params, dtype)
            leaves = _leaf_names(params)
            ce, p = jax.device_get(jax.jit(
                lambda params, t: ref.pass_losses(
                    params, t, self.spec, row_block=row_block,
                    faults=tuple(faults)))(
                params, jnp.asarray(self.first_batches[0])))
            opt = ref.adamw_init(params)
            step = ref.make_train_step(
                self.spec, lr=o["learning_rate"],
                weight_decay=o["weight_decay"], b1=o["b1"], b2=o["b2"],
                eps=o["eps"], faults=tuple(faults), row_block=row_block)
            p0_norm_of = jax.jit(lambda a, b: jax.tree.map(
                lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                    x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
            losses, grad = [], None
            for i, host in enumerate(self.first_batches[:self.checked]):
                params, opt, loss, gnorms = step(params, opt,
                                                 jnp.asarray(host))
                losses.append(float(loss))
                if i == 0:
                    grad = jax.device_get(gnorms)
                if skip_update:
                    # the step donated its state: draw it again, so that
                    # every step starts from the initial one
                    params = ref.init_params(self.key, self.spec)
                    opt = ref.adamw_init(params)
            p0 = ref.init_params(self.key, self.spec)
            update = jax.device_get(p0_norm_of(params, p0))
        return {"loss": losses,
                "grad": np.asarray(jax.tree.leaves(grad), np.float64),
                "update": np.asarray(jax.tree.leaves(update), np.float64),
                "leaves": leaves,
                "pass_losses": np.asarray(ce, np.float64),
                "exit": np.asarray(p, np.float64)}

    def compare(self, program, reference):
        """``lm_train``'s numbers (losses, the first gradient's and the
        whole change's worst leaf) and, of the first batch under the
        initial weights: ``pass_losses``, over the passes the worst mean
        over the positions of the gap between the program's and the
        reference's cross-entropy, against the reference's mean; ``exit``,
        over the passes the worst mean over the positions of the gap
        between the two exit probabilities. Both are 1 at least where the
        two sides count different passes."""
        def worst(name, relative):
            ours, theirs = program[name], reference[name]
            n = min(len(ours), len(theirs))
            gap = np.mean(np.abs(ours[:n] - theirs[:n]), axis=1)
            if relative:
                gap = gap / np.mean(theirs[:n], axis=1)
            return max(float(np.max(gap)),
                       0.0 if len(ours) == len(theirs) else 1.0)

        return super().compare(program, reference) + [
            ("pass_losses", worst("pass_losses", True)),
            ("exit", worst("exit", False))]
