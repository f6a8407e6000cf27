"""Record the small trace the program-name readers' tests read
(``chipbench/tests/data/named.xplane.pb``): three updates of a two-layer
GPT-2 (128 wide, 2 x 512 tokens, AMP bf16, so the flash kernels run)
through ``ShardedTrainStep``, under chipbench's spans.  The HLO copies
(``/host:metadata``) and the planes no reader reads are left out of the
file.  Run on the chip.

    python chipbench/dev/record_named_trace.py <out.xplane.pb>
"""
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

KEEP = ("/device:TPU:0", "/host:CPU")


def strip(src, out):
    """Copy an XSpace (a sequence of length-delimited fields, planes
    being field 1), keeping the planes in ``KEEP`` only."""
    from program_trace import _fields, _text, _varint
    data = memoryview(open(src, "rb").read())
    kept, i = bytearray(), 0
    while i < len(data):
        start = i
        key, i = _varint(data, i)
        if key & 7 != 2:
            raise SystemExit("an XSpace field that is no message")
        size, i = _varint(data, i)
        plane = data[i:i + size]
        i += size
        if key >> 3 != 1 or next((_text(v) for f, _, v in _fields(plane)
                                  if f == 2), "") in KEEP:
            kept += data[start:i]
    with open(out, "wb") as f:
        f.write(kept)


def main(out):
    import jax
    import jax.numpy as jnp
    import numpy as onp
    import mxnet_tpu as mx
    from common import span
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM, GPTModel
    from mxnet_tpu.ops.xent import sparse_softmax_xent
    from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

    mx.amp.init("bfloat16")
    net = GPTForCausalLM(backbone=GPTModel(
        vocab_size=512, units=128, hidden_size=512, num_layers=2,
        num_heads=2, max_length=512, dropout=0.0, embed_dropout=0.0))
    net.initialize()
    mesh = MeshConfig(dp=1)
    train = ShardedTrainStep(
        net, lambda lg, lb: jnp.mean(sparse_softmax_xent(lg, lb)),
        mx.optimizer.create("adam", learning_rate=1e-4), mesh,
        batch_specs=mesh.batch_specs(2, 2), n_labels=1)
    rng = onp.random.default_rng(0)

    def batch():
        t = rng.integers(0, 512, (2, 513), dtype=onp.int32)
        return t[:, :-1], t[:, 1:]

    float(train(*batch()).asnumpy())
    d = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with span("window"):
        for _ in range(3):
            with span("train.step"):
                loss = train(*batch())
        with span("train.fetch"):
            float(loss.asnumpy())
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    print("whole trace", os.path.getsize(found[-1]), "bytes")
    strip(found[-1], out)
    shutil.rmtree(d)
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
