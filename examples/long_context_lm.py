"""Train a tiny causal LM with the sequence axis sharded over the mesh.

The model's attention is exact ring attention
(``fiber_tpu.ops.ring_attention``): each device holds S/n_devices of
the sequence, K/V blocks rotate around the ICI ring with an online
softmax, and jax AD differentiates straight through it (gradient parity
with full-matrix attention is pinned in the test suite). Context length
therefore scales with device count — the long-context plane the
reference framework doesn't have.

The training task is the classic induction probe: the second half of
every sequence repeats the first half, so predicting it well requires
attending ~S/2 tokens back. Watch the half2 loss dive under the half1
(unpredictable) loss as the induction circuit forms.

Run:  python examples/long_context_lm.py [--seq 512] [--steps 300]
      [--attention ring|ulysses|flash]

``--attention flash`` trains through the Pallas flash-attention
kernels: on one device directly (whole sequence in HBM, scores
streamed through VMEM), and on a multi-device mesh as the RING's
per-device block — every rotation runs the kernel and the partial
(out, lse) pairs merge exactly, so context length still scales with
device count while the kernel does the math.
"""

import os as _os
import sys as _sys

_sys.path.insert(
    0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))


import argparse


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=256)
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--vocab", type=int, default=32)
    parser.add_argument("--dim", type=int, default=128)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--attention", default="ring",
                        choices=("ring", "ulysses", "flash"))
    parser.add_argument("--kv-heads", type=int, default=None,
                        help="GQA: KV heads < heads (flash reads the "
                             "small KV natively; XLA planes broadcast)")
    parser.add_argument("--lr", type=float, default=1e-3)
    args = parser.parse_args()

    import jax

    n_dev_check = len(jax.devices())  # every plane shards now
    if args.seq % 2 or args.seq % n_dev_check:
        parser.error(
            f"--seq must be even (copy task halves) and divisible by "
            f"the {n_dev_check}-device mesh; got {args.seq}")
    import jax.numpy as jnp
    import optax

    from fiber_tpu.models import TinyLM, make_train_step
    from fiber_tpu.parallel import default_mesh

    # An explicit mesh makes every plane — flash included — shard the
    # sequence; with mesh=None flash stays single-device.
    mesh = default_mesh() if len(jax.devices()) > 1 else None
    model = TinyLM(vocab=args.vocab, dim=args.dim, heads=8,
                   layers=args.layers, max_seq=args.seq,
                   mesh=mesh, attention=args.attention,
                   kv_heads=args.kv_heads)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adamw(args.lr, weight_decay=0.01)
    opt_state = opt.init(params)
    step = make_train_step(model, opt, batched=True)

    half = args.seq // 2

    def make_batch(key):
        h = jax.random.randint(key, (args.batch, half), 0, args.vocab)
        return jnp.concatenate([h, h], axis=1)

    @jax.jit
    def half_losses(params, tokens):
        def one(t):
            logits = model.apply(params, t)[:-1]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, t[1:][:, None], axis=1)
            return nll[: half - 1].mean(), nll[half - 1:].mean()

        l1, l2 = jax.vmap(one)(tokens)
        return l1.mean(), l2.mean()

    key = jax.random.PRNGKey(1)
    n_dev = len(jax.devices())
    shard = f"{n_dev} devices ({args.seq // n_dev} tokens/device)"
    plane = (shard if args.attention != "flash"
             else "single device, kernels" if n_dev == 1
             else f"ring x flash kernels over {shard}")
    print(f"{args.attention} attention, seq {args.seq} over {plane}")
    for i in range(args.steps):
        key, k = jax.random.split(key)
        tokens = make_batch(k)
        params, opt_state, loss = step(params, opt_state, tokens)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            l1, l2 = half_losses(params, tokens)
            print(f"step {i:4d}  loss {float(loss):5.3f}  "
                  f"half1 {float(l1):5.3f} (random={jnp.log(args.vocab):.3f})  "
                  f"half2 {float(l2):5.3f} <- induction", flush=True)
    print("long-context training done")


if __name__ == "__main__":
    main()
