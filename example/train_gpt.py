"""Pretrain a small GPT with data+tensor parallelism over a device mesh.

Reference parity: the reference's distributed story is
example/distributed_training (kvstore data parallel); this example shows
the TPU-native superset — one `ShardedTrainStep` program compiling
forward + backward + allreduce + optimizer update over a dp×tp
`jax.sharding.Mesh` (megatron column/row specs on the attention/FFN
projections), the way a pod run would.

CPU-friendly: run with a virtual mesh —
    python example/train_gpt.py --cpu-devices 8 --dp 4 --tp 2

Task: character-level language modelling of a repeated-phrase corpus
(synthetic, no downloads); loss falling to ~0 shows the model memorizes.
"""
import argparse
import os
import sys

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM

PHRASE = "the quick brown fox jumps over the lazy dog. "
VOCAB = 128  # ascii


def batches(rng, n, bs, seq):
    text = (PHRASE * (2 + (bs * seq) // len(PHRASE)))
    ids = onp.frombuffer(text.encode(), dtype=onp.uint8).astype("int32")
    for _ in range(n):
        starts = rng.randint(0, len(PHRASE), size=bs)
        tok = onp.stack([ids[s: s + seq + 1] for s in starts])
        yield tok[:, :-1], tok[:, 1:]


def long_context_main(args):
    """Single-device long-context mode: the tied LM head's logits are the
    memory wall (seq 8192 x vocab 50257 ≈ 823 MB bf16), so the loss runs
    through ops.xent.chunked_lm_xent — a lax.scan over vocab chunks with
    an online logsumexp whose VJP re-streams the chunks; logits never
    materialize. Measured on one v5e: gpt2-124m at seq 8192 trains at
    185.6 ms/step (44k tok/s)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import functional
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    from mxnet_tpu.ops.xent import chunked_lm_xent

    mx.random.seed(0)
    net = GPTModel(vocab_size=VOCAB, units=64, hidden_size=128,
                   num_layers=2, num_heads=4, max_length=args.seq_len,
                   dropout=0.0, embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((2, args.seq_len), dtype="int32"))
    trainable, aux = functional.split_params(net)
    opt_m = jax.tree_util.tree_map(jnp.zeros_like, trainable)
    wte = next(n for n in trainable if n.endswith("word_embed.weight"))

    def train_step(tr, m, x, y):
        def f(t):
            hs, _ = functional.functional_call(net, {**t, **aux}, x,
                                               train=True)
            h2 = hs.reshape(-1, hs.shape[-1])
            return jnp.mean(chunked_lm_xent(h2, t[wte], y.reshape(-1),
                                            args.vocab_chunk))
        loss, g = jax.value_and_grad(f)(tr)
        m = jax.tree_util.tree_map(
            lambda a, b: 0.9 * a + b.astype(a.dtype), m, g)
        tr = jax.tree_util.tree_map(lambda w, a: w - 1e-2 * a, tr, m)
        return tr, m, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    rng = onp.random.RandomState(0)
    for i, (x, y) in enumerate(batches(rng, args.steps, args.batch,
                                       args.seq_len)):
        trainable, opt_m, loss = step(trainable, opt_m, jnp.asarray(x),
                                      jnp.asarray(y))
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    print(f"final loss: {float(loss):.4f} (chunked-vocab head, logits "
          "never materialized)")
    assert float(loss) < 1.0, "long-context mode failed to learn"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel mesh size (0 = all devices)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh size")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force an N-device virtual CPU mesh")
    ap.add_argument("--long-context", action="store_true",
                    help="single-device chunked-vocab-xent mode "
                         "(no (N, V) logits; seq 8192 fits one v5e)")
    ap.add_argument("--vocab-chunk", type=int, default=8192)
    args = ap.parse_args()



    import jax

    if args.cpu_devices:
        # a virtual CPU mesh: set before the first backend touch
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)
    if args.long_context:
        if args.steps < 1:
            raise SystemExit("--steps must be >= 1")
        return long_context_main(args)

    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    dp = args.dp or max(1, len(devs) // args.tp)
    if dp * args.tp > len(devs):
        raise SystemExit(f"need {dp * args.tp} devices, have {len(devs)}; "
                         "use --cpu-devices N for a virtual mesh")
    mesh_devs = onp.array(devs[: dp * args.tp],
                          dtype=object).reshape(dp, args.tp)
    mesh = Mesh(mesh_devs, ("dp", "tp"))
    print(f"mesh: dp={dp} x tp={args.tp} on {len(devs)} devices")

    mx.random.seed(0)
    net = GPTForCausalLM(vocab_size=VOCAB, units=64, hidden_size=128,
                         num_layers=2, num_heads=4,
                         max_length=args.seq_len, dropout=0.0,
                         embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((2, args.seq_len), dtype="int32"))  # deferred shapes

    from mxnet_tpu.ops.xent import sparse_softmax_xent
    from mxnet_tpu.parallel import ShardedTrainStep

    def loss_fn(logits, labels):
        import jax.numpy as jnp
        return jnp.mean(sparse_softmax_xent(logits, labels))

    step = ShardedTrainStep(net, loss_fn, "adam", mesh,
                            batch_specs=(P("dp"), P("dp")), n_labels=1)

    if args.steps < 1:
        raise SystemExit("--steps must be >= 1")
    rng = onp.random.RandomState(0)
    for i, (x, y) in enumerate(batches(rng, args.steps, args.batch,
                                       args.seq_len)):
        loss = step(x, y)
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")

    final = float(loss)
    print(f"final loss: {final:.4f} (memorization target < 0.3)")
    assert final < 0.5, "GPT failed to learn the repeated phrase"
    step.save_states("/tmp/gpt_ckpt")  # checkpoint round-trip
    step.load_states("/tmp/gpt_ckpt")
    print("checkpoint save/load ok")


if __name__ == "__main__":
    main()
