"""Runner kind ``lm_train``: the function ``make_train_step`` returns.

One step: dispatch the step on the tokens placed before, draw and place
the next tokens while it runs, wait for its loss. The program jits
without donation, so two states are live inside a step and a second step
in flight would not fit: the loop waits for each step. The first
``checked_steps`` steps of the same compiled step and state are made in
set-up (they are the warm-up) and are what the plain reference follows.
"""

from __future__ import annotations

import importlib

import numpy as np


def _leaf_names(tree):
    import jax

    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def model_kwargs(cfg):
    return dict(vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                layers=cfg["num_hidden_layers"],
                mlp_mult=cfg["intermediate_size"] // cfg["hidden_size"])


def make_step(cfg, traffic, devices, rehearsal=False):
    """The program's objects for the cell: (model, optimizer, the function
    ``make_train_step`` returns, where arrays are placed)."""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from fiber_tpu.models import TinyLM, make_train_step

    if cfg["intermediate_size"] % cfg["hidden_size"]:
        raise ValueError("the MLP width is no multiple of the hidden size")
    mesh = (Mesh(np.asarray(devices), ("pool",)) if traffic["mesh"]
            else Mesh(np.asarray(devices[:1]), ("pool",)))
    model = TinyLM(max_seq=int(traffic["seq"]), pos="rope",
                   attention=traffic["attention"],
                   window=(int(cfg["sliding_window"])
                           if traffic["use_window"] else None),
                   mesh=mesh, interpret=rehearsal, **model_kwargs(cfg))
    o = cfg["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"no optimizer {o['name']!r} here")
    opt = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                      eps=o["eps"], weight_decay=o["weight_decay"])
    step = make_train_step(model, opt, batched=bool(int(traffic["batch"])))
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

    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    tokens = jax.ShapeDtypeStruct((batch, seq) if batch else (seq,),
                                  jnp.int32, sharding=place)
    return step.lower(placed(params), placed(opt_state), tokens)


class Runner:
    rate = "train_tokens_per_s"   # the end-to-end metric: tokens a second

    def __init__(self, cfg, traffic, key, seed, devices, spans,
                 rehearsal=False):
        self.cfg, self.traffic = cfg, traffic
        self.key = key
        self.devices = devices
        self.span = spans
        self.rehearsal = rehearsal
        self.seq = int(traffic["seq"])
        self.batch = int(traffic["batch"])
        self.checked = int(traffic["checked_steps"])
        self.window = (int(cfg["sliding_window"])
                       if traffic["use_window"] else None)
        self.rng = np.random.default_rng(seed)
        self.first_batches = []
        self.program = {}

    # -- set-up ----------------------------------------------------------
    def build(self):
        import jax

        model, self.opt, self.step, self.place = make_step(
            self.cfg, self.traffic, self.devices, self.rehearsal)
        # weights on the device(s), in one jitted call from the seed
        self.init = jax.jit(model.init, out_shardings=self.place)
        self.params = self.init(self.key)
        self.opt_state = jax.jit(self.opt.init)(self.params)
        self.next_tokens = self._make_batch()

    def _draw(self):
        """The next step's tokens on the host; the first ``checked_steps``
        draws are kept for the reference."""
        shape = (self.batch, self.seq) if self.batch else (self.seq,)
        host = self.rng.integers(0, self.cfg["vocab_size"], shape,
                                 dtype=np.int32)
        if len(self.first_batches) < self.checked:
            self.first_batches.append(host)
        return host

    def _make_batch(self):
        import jax

        with self.span("make_batch"):
            return jax.device_put(self._draw(), self.place)

    def draw_checked_batches(self):
        """The checked steps' tokens without the program: for a reference
        that runs apart from it (tools/readings.py ``--side reference``)."""
        while len(self.first_batches) < self.checked:
            self._draw()

    def units_per_call(self) -> int:
        return self.seq * max(self.batch, 1)

    def one_step(self):
        import jax

        with self.span("dispatch"):
            self.params, self.opt_state, loss = self.step(
                self.params, self.opt_state, self.next_tokens)
        self.next_tokens = self._make_batch()
        with self.span("wait"):
            jax.block_until_ready(loss)
        return loss

    def checked_steps(self):
        """Drive the first steps and keep the program's side of the check:
        each step's loss, the norm of each leaf of the first gradient as
        the optimizer got it (mu / (1 - b1) after one update), and the norm
        of each leaf's change after the steps."""
        import jax
        import jax.numpy as jnp

        b1 = self.cfg["optimizer"]["b1"]
        norms = jax.jit(lambda tree, scale: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))) * scale, tree))
        moved = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
        losses, grad = [], None
        for i in range(self.checked):
            losses.append(float(self.one_step()))
            if i == 0:
                mu = next(s.mu for s in self.opt_state if hasattr(s, "mu"))
                grad = jax.device_get(norms(mu, 1.0 / (1.0 - b1)))
        p0 = self.init(self.key)
        update = jax.device_get(moved(self.params, p0))
        del p0
        self.program = {
            "loss": losses,
            "grad": np.asarray(jax.tree.leaves(grad), np.float64),
            "update": np.asarray(jax.tree.leaves(update), np.float64),
            "leaves": _leaf_names(self.params),
        }

    # -- window ----------------------------------------------------------
    def call(self) -> int:
        self.one_step()
        return self.units_per_call()

    def free(self):
        self.params = self.opt_state = self.step = self.next_tokens = None
        self.init = None

    # -- the check ---------------------------------------------------------
    def reference(self, dtype=None, seq_block=None, loss_tokens=None,
                  skip_update=False):
        """The plain reference over the checked steps, on one device, from
        the same seed and tokens. ``dtype`` is the control's;
        ``seq_block``, ``loss_tokens`` and ``skip_update`` are faults'; the
        benchmark's own runs pass none of them."""
        import jax
        import jax.numpy as jnp

        ref = importlib.import_module(self.cfg["reference"])

        cfg, o = self.cfg, self.cfg["optimizer"]
        kw = model_kwargs(self.cfg)
        with jax.default_device(self.devices[0]):
            params = ref.init_params(self.key, **kw)
            if dtype is not None:
                params = ref.cast(params, dtype)
            leaves = _leaf_names(params)
            opt = ref.adamw_init(params)
            p0_norm_of = jax.jit(lambda a, b: jax.tree.map(
                lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                    x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
            losses, grad = [], None
            for i, host in enumerate(self.first_batches[:self.checked]):
                if skip_update and i > 0:
                    losses.append(losses[-1])
                    continue
                params, opt, loss, gnorms = ref.train_step(
                    params, opt, jnp.asarray(host), lr=o["learning_rate"],
                    weight_decay=o["weight_decay"], b1=o["b1"], b2=o["b2"],
                    eps=o["eps"], heads=kw["heads"], kv_heads=kw["kv_heads"],
                    window=self.window, seq_block=seq_block,
                    loss_tokens=loss_tokens,
                    row_block=self.traffic.get("reference_row_block"))
                losses.append(float(loss))
                if i == 0:
                    grad = jax.device_get(gnorms)
            p0 = ref.init_params(self.key, **kw)
            update = jax.device_get(p0_norm_of(params, p0))
        return {"loss": losses,
                "grad": np.asarray(jax.tree.leaves(grad), np.float64),
                "update": np.asarray(jax.tree.leaves(update), np.float64),
                "leaves": leaves}

    def compare(self, program, reference):
        """[(name, value), ...]. Losses by their relative gap. Gradient and
        update by the worst leaf: the gap between the program's norm and
        the reference's, against the reference's norm of that leaf or of
        the median leaf, whichever is larger. Leaves whose reference
        gradient is under a thousandth of the median leaf's move by
        round-off alone and are left out of the update."""
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
        u_floor = np.maximum(u_r, np.median(u_r[live]))
        out.append(("update", float(np.max(
            (np.abs(program["update"] - u_r) / u_floor)[live]))))
        return out
