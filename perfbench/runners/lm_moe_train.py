"""Runner kind ``lm_moe_train``: the function ``make_train_step`` returns for
a model built from a description of its layers (``fiber_tpu.models.BlockLM``):
window and full attention layers with their own head counts and ropes, a
gated dense MLP, sparse-expert layers of which this chip holds a share.

The configuration's file holds the published keys; ``workmodel_moe.describe``
turns the first ``num_hidden_layers`` layers into plain data, from which the
program's ``Block``s are built here and which the plain reference is handed
as it is.
The step is made with ``donate=True`` (parameters and AdamW state are live
once in a step; the configuration does not fit otherwise); the loop is
``lm_train``'s (``runners/lm_train.py`` ``Runner``, subclassed here), one
step in flight. The first ``checked_steps`` steps are made in
set-up (they are the warm-up) and are what the reference follows; before
the first of them the program's ``probe_routing`` gives the experts each
token of the first batch takes and each held expert's load.
"""

from __future__ import annotations

import importlib

import numpy as np

from workmodel_moe import describe

lm_train = importlib.import_module("runners.lm_train")
_leaf_names = lm_train._leaf_names


def blocks_of(spec, chunk_rows):
    """The program's description of the same layers."""
    from fiber_tpu.models import Block, Experts, Rope, Yarn

    def rope(r):
        return Rope(base=r["base"], rotary=r["rotary"],
                    yarn=Yarn(**r["yarn"]) if r["yarn"] else None)

    return [Block(heads=layer["heads"], window=layer["window"],
                  rope=rope(layer["rope"]), ffn=layer["ffn"],
                  width=layer.get("width", 0),
                  experts=(Experts(share=spec["share"], chunk_rows=chunk_rows,
                                   **layer["experts"])
                           if layer["ffn"] == "experts" else None))
            for layer in spec["layers"]]


def make_step(cfg, traffic, devices, rehearsal=False):
    """The program's objects for the cell: (model, optimizer, the function
    ``make_train_step`` returns, where arrays are placed)."""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from fiber_tpu.models import BlockLM, make_train_step

    if traffic["mesh"] or int(traffic["batch"]):
        raise ValueError("lm_moe_train runs one sequence a step on one chip")
    spec = describe(cfg, bool(traffic["use_window"]))
    mesh = Mesh(np.asarray(devices[:1]), ("pool",))
    model = BlockLM(blocks_of(spec, int(cfg["dispatch_chunk_rows"])),
                    vocab=spec["vocab"], dim=spec["dim"],
                    head_dim=spec["head_dim"], kv_heads=spec["kv_heads"],
                    max_seq=int(traffic["seq"]),
                    attention=traffic["attention"], mesh=mesh,
                    interpret=rehearsal)
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
    around this kind's model, probe, reference and comparison."""

    def __init__(self, cfg, traffic, key, seed, devices, spans,
                 rehearsal=False):
        super().__init__(cfg, traffic, key, seed, devices, spans,
                         rehearsal=rehearsal)
        self.spec = describe(cfg, bool(traffic["use_window"]))

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
        """``lm_train``'s side of the check (each step's loss, the first
        gradient's and the whole change's norm of each leaf), and before
        the first step the experts each token of the first batch takes
        under the initial weights, with each held expert's load."""
        found = self.model.probe_routing(self.params, self.next_tokens)
        super().checked_steps()
        self.program["taken"] = np.sort(np.asarray(found["ids"]), axis=-1)
        self.program["load"] = np.asarray(found["load"])

    def free(self):
        super().free()
        self.model = None

    # -- the check ---------------------------------------------------------
    def reference(self, dtype=None, faults=(), skip_update=False):
        """The plain reference over the checked steps, on one device, from
        the same seed and tokens. ``dtype`` is the control's; ``faults``
        (names the reference's head lists) and ``skip_update`` are the
        planted faults'; the benchmark's own runs pass none of them."""
        import jax
        import jax.numpy as jnp

        ref = importlib.import_module(self.cfg["reference"])

        o = self.cfg["optimizer"]
        with jax.default_device(self.devices[0]):
            params = ref.init_params(self.key, self.spec)
            if dtype is not None:
                params = ref.cast(params, dtype)
            leaves = _leaf_names(params)
            opt = ref.adamw_init(params)
            step = ref.make_train_step(
                self.spec, lr=o["learning_rate"],
                weight_decay=o["weight_decay"], b1=o["b1"], b2=o["b2"],
                eps=o["eps"], faults=tuple(faults),
                row_block=self.traffic.get("reference_row_block"))
            p0_norm_of = jax.jit(lambda a, b: jax.tree.map(
                lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                    x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
            losses, grad, taken = [], None, None
            for i, host in enumerate(self.first_batches[:self.checked]):
                if skip_update and i > 0:
                    losses.append(losses[-1])
                    continue
                params, opt, loss, gnorms, ids = step(
                    params, opt, jnp.asarray(host))
                losses.append(float(loss))
                if i == 0:
                    grad = jax.device_get(gnorms)
                    taken = np.asarray(jax.device_get(ids))
            p0 = ref.init_params(self.key, self.spec)
            update = jax.device_get(p0_norm_of(params, p0))
        return {"loss": losses,
                "grad": np.asarray(jax.tree.leaves(grad), np.float64),
                "update": np.asarray(jax.tree.leaves(update), np.float64),
                "leaves": leaves, "taken": taken}

    def compare(self, program, reference):
        """[(name, value), ...]. Losses by their relative gap. Gradient and
        update by the worst leaf: the gap between the program's norm and
        the reference's, against the reference's norm of that leaf or of
        the median leaf, whichever is larger (leaves whose reference
        gradient is under a thousandth of the median leaf's are left out of
        the update). The update is read apart for the leaves that every
        token reaches (``update``) and for the routers and the routed
        experts' matrices (``update_routed``): AdamW moves an element by
        about the learning rate however small its gradient, so an expert
        that gets no token on one side and one token on the other (a
        near-tie in the router falling the other way) moves a whole
        expert's elements on one side only. ``routing``: the share of
        (token, expert layer) pairs whose set of taken experts differs, on
        the first batch under the initial weights."""
        if program["leaves"] != reference["leaves"]:
            raise ValueError("program and reference name different leaves")
        out = [(f"loss{i + 1}", abs(a - b) / abs(b))
               for i, (a, b) in enumerate(zip(program["loss"],
                                              reference["loss"]))]
        g_r, u_r = reference["grad"], reference["update"]
        g_floor = np.maximum(g_r, np.median(g_r))
        out.append(("grad", float(np.max(
            np.abs(program["grad"] - g_r) / g_floor))))
        live = g_r >= 1e-3 * np.median(g_r)
        u_gap = np.abs(program["update"] - u_r) / np.maximum(
            u_r, np.median(u_r[live]))
        routed = np.asarray(["experts_w" in name or "router" in name
                             for name in reference["leaves"]])
        out.append(("update", float(np.max(u_gap[live & ~routed]))))
        out.append(("update_routed", float(np.max(u_gap[live & routed]))))
        out.append(("routing", float(np.mean(np.any(
            program["taken"] != reference["taken"], axis=-1)))))
        return out
