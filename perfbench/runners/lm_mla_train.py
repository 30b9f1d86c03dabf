"""Runner kind ``lm_mla_train``: the function ``make_train_step`` returns for
a model of latent-attention layers (``fiber_tpu.models.Latent``) with a
leading dense layer, sparse-expert layers of which this chip holds a share,
and a multi-token-prediction module (``fiber_tpu.models.MTP``).

The configuration's file holds the published keys (DeepSeek-V3's key set);
``workmodel_mla.describe`` turns them into plain data, from which the
program's ``Block``s are built here and which the plain reference is handed
as it is. The donating step, the loop with one step in flight, the routing
probe before the first checked step, the reference's three steps and the
comparison (losses, gradient, update apart for the routed leaves, routing)
are ``lm_moe_train``'s and ``lm_train``'s (``Runner``, subclassed here).
"""

from __future__ import annotations

import importlib

import numpy as np

from workmodel_mla import describe

lm_train = importlib.import_module("runners.lm_train")
lm_moe_train = importlib.import_module("runners.lm_moe_train")


def model_of(cfg, seq, attention, mesh, rehearsal=False):
    """The program's model of the configuration's layers."""
    from fiber_tpu.models import MTP, Block, BlockLM, Experts, Latent, Rope

    spec = describe(cfg)
    latent = Latent(q_rank=spec["q_rank"], kv_rank=spec["kv_rank"],
                    nope=spec["nope"], rope_dim=spec["rope_dim"],
                    v_dim=spec["v_dim"])
    rope = Rope(base=spec["rope_base"], interleaved=spec["interleaved"])
    chunk_rows = int(cfg["dispatch_chunk_rows"])

    def block(layer):
        return Block(heads=spec["heads"], mixer="latent", latent=latent,
                     rope=rope, ffn=layer["ffn"],
                     width=layer.get("width", 0),
                     experts=(Experts(share=spec["share"],
                                      chunk_rows=chunk_rows,
                                      **layer["experts"])
                              if layer["ffn"] == "experts" else None))

    m = spec["mtp"]
    recompute = cfg["recompute"]
    return BlockLM([block(layer) for layer in spec["layers"]],
                   vocab=spec["vocab"], dim=spec["dim"],
                   head_dim=spec["nope"] + spec["rope_dim"],
                   kv_heads=spec["heads"], max_seq=seq, attention=attention,
                   mesh=mesh,
                   interpret=rehearsal, norm_eps=spec["norm_eps"],
                   recompute=recompute["layers"],
                   head_block=recompute["head_block_rows"],
                   mtp=MTP(block=block(m["layer"]), depth=m["depth"],
                           weight=m["weight"]))


def make_step(cfg, traffic, devices, rehearsal=False):
    """The program's objects for the cell: (model, optimizer, the function
    ``make_train_step`` returns, where arrays are placed)."""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from fiber_tpu.models import make_train_step

    if traffic["mesh"] or int(traffic["batch"]):
        raise ValueError("lm_mla_train runs one sequence a step on one chip")
    mesh = Mesh(np.asarray(devices[:1]), ("pool",))
    model = model_of(cfg, int(traffic["seq"]), traffic["attention"], mesh,
                     rehearsal)
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
    """``lm_moe_train``'s loop, probe, reference and comparison around this
    kind's model."""

    def __init__(self, cfg, traffic, key, seed, devices, spans,
                 rehearsal=False):
        # the model has no window, whatever the mix says
        lm_train.Runner.__init__(self, cfg, dict(traffic, use_window=False),
                                 key, seed, devices, spans,
                                 rehearsal=rehearsal)
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
