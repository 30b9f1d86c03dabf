"""Runner kind ``lm_hybrid_train``: the function ``make_train_step`` returns
for a model whose every layer is one part (``fiber_tpu.models.BlockLM``): a
state-space mixer, attention without a position scheme, or sparse experts of
the ungated relu^2 form of which this chip holds a share.

The configuration's file holds the published keys (``nemotron_h``'s);
``workmodel_hybrid.describe`` turns the first ``num_hidden_layers`` layers of
its pattern into plain data, from which the program's ``Block``s are built
here and which the plain reference is handed as it is. Everything else is
``lm_moe_train``'s (``runners/lm_moe_train.py`` ``Runner``, subclassed here):
the donating step, the loop with one step in flight, the probe of the first
batch's routing, the reference's three steps and the comparison, to which
this kind adds one number, ``positions``: the first batch's loss under the
initial weights, position by position. A mean loss cannot see a scan that
forgets its state between blocks (under random weights each position's loss
moves either way and the mean stays); the first positions of every later
block can.
"""

from __future__ import annotations

import importlib

import numpy as np

import workmodel_hybrid
from workmodel_hybrid import describe

lm_moe_train = importlib.import_module("runners.lm_moe_train")


def blocks_of(spec, cfg):
    """The program's description of the same layers."""
    from fiber_tpu.models import Block, Experts, StateSpace

    def block(layer):
        layer = dict(layer)
        kind = layer.pop("kind")
        if kind == "ssm":
            return Block(mixer="ssm", ffn=None, ssm=StateSpace(
                recompute=bool(cfg["recompute"]["ssm"]), **layer))
        if kind == "attention":
            return Block(heads=layer["heads"], rope=None, ffn=None)
        return Block(mixer=None, ffn="experts", experts=Experts(
            share=spec["share"], kind="relu2",
            chunk_rows=int(cfg["dispatch_chunk_rows"]), **layer))

    return [block(layer) for layer in spec["layers"]]


def make_step(cfg, traffic, devices, rehearsal=False):
    """The program's objects for the cell: (model, optimizer, the function
    ``make_train_step`` returns, where arrays are placed)."""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from fiber_tpu.models import BlockLM, make_train_step

    if traffic["mesh"] or int(traffic["batch"]):
        raise ValueError(
            "lm_hybrid_train runs one sequence a step on one chip")
    spec = describe(cfg)
    mesh = Mesh(np.asarray(devices[:1]), ("pool",))
    model = BlockLM(blocks_of(spec, cfg), vocab=spec["vocab"],
                    dim=spec["dim"], head_dim=spec["head_dim"],
                    kv_heads=spec["kv_heads"], max_seq=int(traffic["seq"]),
                    attention=traffic["attention"], pos="none", mesh=mesh,
                    interpret=rehearsal, norm_eps=spec["norm_eps"])
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


class Runner(lm_moe_train.Runner):
    """``lm_moe_train``'s runner around this kind's description and step."""

    def __init__(self, cfg, traffic, key, seed, devices, spans,
                 rehearsal=False):
        # past ``lm_moe_train``'s own, which reads Laguna's keys; the model
        # has no window (``sliding_window`` is null), whatever the mix says
        lm_moe_train.lm_train.Runner.__init__(
            self, cfg, dict(traffic, use_window=False), key, seed, devices,
            spans, rehearsal=rehearsal)
        self.spec = describe(cfg)

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
        """``lm_moe_train``'s side of the check and, before the first step,
        each position's loss of the first batch under the initial weights."""
        import jax

        by_position = jax.device_get(jax.jit(self.model.token_losses)(
            self.params, self.next_tokens))
        super().checked_steps()
        self.program["positions"] = np.asarray(by_position, np.float64)

    # -- the check ---------------------------------------------------------
    def reference(self, dtype=None, faults=(), skip_update=False):
        """``lm_moe_train``'s, and the reference's loss of the first batch
        under the initial weights, position by position."""
        import jax
        import jax.numpy as jnp

        out = super().reference(dtype=dtype, faults=faults,
                                skip_update=skip_update)
        ref = importlib.import_module(self.cfg["reference"])
        with jax.default_device(self.devices[0]):
            params = ref.init_params(self.key, self.spec)
            if dtype is not None:
                params = ref.cast(params, dtype)
            by_position = jax.jit(
                lambda p, t: ref.position_losses(
                    p, t, self.spec, faults=tuple(faults),
                    row_block=self.traffic.get("reference_row_block")))(
                params, jnp.asarray(self.first_batches[0]))
            out["positions"] = np.asarray(jax.device_get(by_position),
                                          np.float64)
        return out

    def compare(self, program, reference):
        """``lm_moe_train``'s numbers and ``positions``: the median, over
        the first eighth of every block of the scan after the first, of the
        gap between the program's and the reference's loss of a position,
        against the reference's. Those are the positions where all that is
        known of the earlier blocks comes through the carried state (a scan
        that forgets it reads fifteen times a sound run there, PERF.md
        section 4); the median, because a sound run's gaps have a long tail
        (the tokens whose taken experts differ, ``routing``), which a mean
        would read instead."""
        chunk = max(layer["chunk"] for layer
                    in workmodel_hybrid.ssm_layers(self.spec))
        at = np.arange(len(reference["positions"]))
        at = at[(at >= chunk) & (at % chunk < chunk // 8)]
        ours, theirs = program["positions"][at], reference["positions"][at]
        return super().compare(program, reference) + [
            ("positions", float(np.median(np.abs(ours - theirs) / theirs)))]
