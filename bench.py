"""Benchmark: training/inference throughput with MFU accounting, one chip.

Mirrors the reference's headline grid (BASELINE.md, from
docs/static_site/src/pages/api/faq/perf.md:150-254): ResNet-50 train
(fp32 + bf16), ResNet-50 inference (bf16), BERT-base pretraining (bf16,
two batch sizes).  The north star (BASELINE.json) is MFU, reported as
**model FLOPs** / measured time / chip bf16 peak:

- ResNet-50: 4.09 GFLOP/image forward at 224x224 (standard count,
  mul+add=2), x3 for training (fwd + 2x bwd).
- BERT: 6 * params * tokens for training (the 6ND rule).

XLA's cost_analysis is recorded per row as xla_flops_per_step (it counts
a scan body once, so for fused-loop rows it is already per-step); MFU uses
the analytic model-FLOPs number.

Measurement method: training rows run K steps fused into ONE executable
via mx.parallel.scan_steps (lax.scan over stacked batches), so one launch
carries K steps the way a production input pipeline would.  Timing chains
state through donated params and closes the window with a single host
fetch of the final loss, which waits for the whole chain.  Windows
>= ~1.2 s.

Device rule: the grid runs on the accelerator JAX finds.  With none, the
process exits non-zero — unless the caller asked for the CPU with
JAX_PLATFORMS=cpu (the ci/run.sh contracts smoke), which is honoured at
toy shapes and labelled ``"platform": "cpu"``.  A row that raises ends
the run: the rows measured so far are printed, the process exits
non-zero.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} with
the full grid in "grid".
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

# reference V100 grids by batch size (BASELINE.md, perf.md:150-254)
BASE_R50_TRAIN = {1: 34.54, 16: 251.22, 32: 298.51, 64: 343.19, 128: 363.69}
BASE_R50_INFER_FP16 = {1: 270.89, 32: 2085.51, 128: 2355.04}
BASE_INCEPTION_TRAIN = {1: 21.83, 16: 173.15, 32: 214.48, 64: 247.43,
                        128: 253.68}

BASELINE_TRAIN_IMG_S = BASE_R50_TRAIN[32]   # headline comparison row
BASELINE_INFER_IMG_S = 1076.81  # reference V100 bs=32 ResNet-50 inference fp32

RESNET50_MACS_PER_IMG = 4.089e9          # fvcore count at 224x224
RESNET50_INFER_FLOPS_PER_IMG = 2 * RESNET50_MACS_PER_IMG
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * RESNET50_INFER_FLOPS_PER_IMG  # fwd+2xbwd
INCEPTION3_MACS_PER_IMG = 5.73e9         # fvcore count at 299x299
INCEPTION3_TRAIN_FLOPS_PER_IMG = 3 * 2 * INCEPTION3_MACS_PER_IMG

def _measure(step, args, n_state: int, target_s: float = 1.2,
             max_iters: int = 400):
    """Time `step` by chaining iterations through its first n_state outputs.

    Returns (seconds_per_call, final_scalar). The final output of `step`
    must be a scalar whose host fetch forces completion of the whole chain.
    """
    state, rest = list(args[:n_state]), list(args[n_state:])

    def run(iters):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(*state, *rest)
            state = list(out[:n_state])
        val = float(out[-1])  # single host fetch: syncs the full chain
        return time.perf_counter() - t0, val

    run(3)                       # warmup (compile + first dispatches)
    dt, _ = run(5)               # pilot to calibrate the window
    iters = min(max_iters, max(6, math.ceil(target_s / max(dt / 5, 1e-5))))
    dt, val = run(iters)
    from mxnet_tpu import goodput as _goodput
    if _goodput._active:
        # the measured window is pure device compute in the ledger
        _goodput.note("compute", dt)
    return dt / iters, val


def _compile(jitted, *abstract_args):
    """Compile once; return (callable, cost) so the timed path reuses
    the same executable instead of paying a second trace+compile.
    ``cost`` is mx.insight's normalised cost_analysis capture
    ({"flops", "bytes_accessed", ...}; {} when the backend reports
    none) — the same analysis basis as the live /insight plane."""
    from mxnet_tpu import insight as _insight
    comp = jitted.lower(*abstract_args).compile()
    return comp, _insight.capture_cost(comp)


def _cast_tree(tree, dtype):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, tree)


def _row(name, sec_per_step, items_per_step, model_flops_per_step,
         precision, peak, cost=None):
    row = {"name": name, "items_per_s": items_per_step / sec_per_step,
           "ms_per_step": sec_per_step * 1e3, "precision": precision,
           "model_flops_per_step": model_flops_per_step}
    cost = cost or {}
    xla_flops = cost.get("flops")
    if xla_flops:
        row["xla_flops_per_step"] = xla_flops
    xla_bytes = cost.get("bytes_accessed")
    if xla_bytes:
        row["xla_bytes_accessed_per_step"] = xla_bytes
    if xla_flops and xla_bytes:
        from mxnet_tpu import insight as _insight
        row["bound"] = _insight.roofline_verdict(xla_flops, xla_bytes,
                                                 step_seconds=sec_per_step)
    if peak:
        eff = model_flops_per_step / sec_per_step
        row["effective_tflops"] = round(eff / 1e12, 2)
        row["mfu"] = round(eff / peak, 4)
        # a reading above peak means the timing window is broken —
        # report it as invalid rather than as a throughput.
        row["valid"] = eff <= peak
    from mxnet_tpu import goodput as _goodput
    if _goodput._active:
        # goodput_fraction + top-2 badput causes for this row's window
        # (main() resets the ledger per row)
        row.update(_goodput.bench_fields())
    return row


def _config_dict(batch, steps_per_call, zero=0, grad_accum=1, remat=False,
                 prefetch_depth=None):
    """The full step-config a row actually ran under, in the same shape
    mx.autotune persists — so bench rows and tuned winners join cleanly."""
    return {"batch": batch, "steps_per_call": steps_per_call, "zero": zero,
            "grad_accum": grad_accum, "remat": remat,
            "prefetch_depth": prefetch_depth}


def _bench_cnn_train(model_ctor, name, macs_per_img, native_size,
                     precision, on_cpu, peak, k_steps=16, tpu_cfg=(32, None),
                     cpu_cfg=(4, 64, 100), nclass_tpu=1000,
                     baseline_img_s=None):
    """Shared CNN training bench: momentum-SGD step fused K-per-launch.

    The ~160 1-D parameter/stat vectors (BN gamma/beta/running stats,
    biases) are packed into single contiguous vectors (functional.Packer)
    so cast + momentum + SGD lower to a few large fused ops instead of
    hundreds of tiny ones — profiled at ~0.5 ms/step on ResNet-50.
    """
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import functional
    from mxnet_tpu.parallel import scan_steps

    if on_cpu:
        bs, size, nclass = cpu_cfg
        k_steps = 2
    else:
        bs = tpu_cfg[0]
        size = tpu_cfg[1] or native_size
        nclass = nclass_tpu
    cdtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

    net = model_ctor(classes=nclass)
    net.initialize()
    net(mx.np.zeros((bs, 3, size, size), dtype="float32"))
    trainable, aux = functional.split_params(net)
    t_pack = functional.Packer(trainable)
    a_pack = functional.Packer(aux)
    tvec, tbig = t_pack.pack(trainable)
    aux_pk = a_pack.pack(aux)
    mom = (jnp.zeros_like(tvec), jax.tree_util.tree_map(jnp.zeros_like, tbig))

    def train_step(tvec, tbig, aux_pk, mom, x, y):
        avec, abig = aux_pk

        # mixed precision: fp32 master weights, compute cast inside the step
        def loss_fn(tvec, tbig):
            tr = t_pack.unpack(tvec.astype(cdtype), _cast_tree(tbig, cdtype))
            aux_d = a_pack.unpack(avec, abig)
            from mxnet_tpu.ops.xent import sparse_softmax_xent
            logits, mutated = functional.functional_call(
                net, {**tr, **aux_d}, x.astype(cdtype), train=True)
            loss = jnp.mean(sparse_softmax_xent(logits, y))
            return loss, mutated
        (loss, mutated), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(tvec, tbig)
        gvec, gbig = grads
        mvec = 0.9 * mom[0] + gvec
        mbig = jax.tree_util.tree_map(
            lambda m, g: 0.9 * m + g.astype(m.dtype), mom[1], gbig)
        tvec = tvec - 0.05 * mvec
        tbig = jax.tree_util.tree_map(lambda w, m: w - 0.05 * m, tbig, mbig)
        aux_d = a_pack.unpack(avec, abig)
        aux_pk = a_pack.pack({**aux_d, **mutated})
        return tvec, tbig, aux_pk, (mvec, mbig), loss

    step = jax.jit(scan_steps(train_step, n_state=4),
                   donate_argnums=(0, 1, 2, 3))
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    xs = jax.random.normal(kx, (k_steps, bs, 3, size, size), jnp.float32)
    ys = jax.random.randint(ky, (k_steps, bs), 0, nclass)
    step, cost = _compile(
        step, tvec, tbig, aux_pk, mom,
        jax.ShapeDtypeStruct(xs.shape, xs.dtype),
        jax.ShapeDtypeStruct(ys.shape, ys.dtype))
    sec, _ = _measure(step, (tvec, tbig, aux_pk, mom, xs, ys), n_state=4)
    sec /= k_steps
    flops = bs * 3 * 2 * macs_per_img * (size / native_size) ** 2
    row = _row(f"{name}_train_bs{bs}_{precision}", sec, bs, flops,
               precision, peak, cost=cost)
    row["steps_per_call"] = k_steps
    row["config"] = _config_dict(bs, k_steps)
    from mxnet_tpu import config as _cfg
    row["fused_conv_bn"] = str(_cfg.get("fused_conv_bn"))
    if baseline_img_s:
        row["vs_v100_baseline"] = round(bs / sec / baseline_img_s, 2)
    return row


def bench_resnet50_train(precision: str, on_cpu: bool, peak, k_steps=None,
                         bs=32):
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    # stacked k-step input must stay modest at large batch (HBM)
    k_steps = k_steps or max(2, min(16, 512 // bs))
    return _bench_cnn_train(resnet50_v1, "resnet50", RESNET50_MACS_PER_IMG,
                            224, precision, on_cpu, peak, k_steps,
                            tpu_cfg=(bs, None),
                            baseline_img_s=BASE_R50_TRAIN.get(bs))


def bench_inception_train(precision: str, on_cpu: bool, peak, k_steps=None,
                          bs=32):
    """Inception-v3 training (BASELINE.md: 214.48 img/s bs32 on V100)."""
    from mxnet_tpu.gluon.model_zoo.vision import inception_v3
    k_steps = k_steps or max(2, min(16, 512 // bs))
    return _bench_cnn_train(inception_v3, "inception_v3",
                            INCEPTION3_MACS_PER_IMG, 299, precision, on_cpu,
                            peak, k_steps, tpu_cfg=(bs, None),
                            cpu_cfg=(2, 75, 10),
                            baseline_img_s=BASE_INCEPTION_TRAIN.get(bs))


def bench_resnet50_infer(precision: str, on_cpu: bool, peak, k_steps=16,
                         bs=32):
    """bf16/fp32 inference; precision='int8' routes through post-training
    quantization (contrib.quantization) and scores against the chip's
    int8 peak (mx.insight.PEAKS — v4 has no int8 doubling)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import functional
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import scan_steps

    size = 224
    if on_cpu:
        bs, size, k_steps = 4, 64, 2
    int8 = precision == "int8"
    cdtype = jnp.float32 if int8 else (
        jnp.bfloat16 if precision == "bf16" else jnp.float32)

    net = resnet50_v1()
    net.initialize()
    if int8:
        from mxnet_tpu.contrib import quantization as q
        calib = mx.np.array(onp.random.RandomState(0)
                            .rand(bs, 3, size, size).astype("float32"))
        net = q.quantize_net(net, calib_data=[calib], calib_mode="naive")
        params = functional.param_arrays(net)
        from mxnet_tpu import insight as _insight
        bf16_peak, int8_peak, _ = _insight.peaks()
        int8_factor = int8_peak / bf16_peak
        peak = peak and peak * int8_factor
    else:
        net(mx.np.zeros((bs, 3, size, size), dtype="float32"))
        params = _cast_tree(functional.param_arrays(net), cdtype)

    def fwd(carry, x):
        # `carry` threads a data dependency so chained calls serialize
        out, _ = functional.functional_call(
            net, params, x + carry.astype(x.dtype), train=False)
        return jnp.max(out).astype(jnp.float32), jnp.sum(out, dtype=jnp.float32)

    step = jax.jit(scan_steps(fwd, n_state=1))
    xs = jax.random.normal(jax.random.PRNGKey(0),
                           (k_steps, bs, 3, size, size), cdtype)
    step, cost = _compile(step, jax.ShapeDtypeStruct((), jnp.float32),
                          jax.ShapeDtypeStruct(xs.shape, xs.dtype))
    sec, _ = _measure(step, (jnp.zeros(()), xs), n_state=1)
    sec /= k_steps
    flops = bs * RESNET50_INFER_FLOPS_PER_IMG * (size / 224.0) ** 2
    row = _row(f"resnet50_infer_bs{bs}_{precision}", sec, bs, flops,
               precision, peak, cost=cost)
    row["steps_per_call"] = k_steps
    row["config"] = _config_dict(bs, k_steps)
    # every inference row names its peak basis so cross-precision MFU
    # comparisons of the grid are self-describing
    if int8:
        row["peak_basis"] = f"int8 ({int8_factor:g}x bf16)"
        from mxnet_tpu import config as _cfg
        row["quant_config"] = {
            "scheme": "int8_sym_perchannel", "calib_mode": "naive",
            "activations": "int8", "weights": "int8",
            "fused_matmul": _cfg.get("quantize.fused_matmul")}
    else:
        row["peak_basis"] = "bf16"
    base = BASE_R50_INFER_FP16.get(bs)
    if base and not on_cpu and not int8:
        row["vs_v100_fp16_baseline"] = round(bs / sec / base, 2)
    return row


def bench_bert_train(precision: str, on_cpu: bool, peak, bs=32, k_steps=16,
                     dropout=0.0):
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import functional
    from mxnet_tpu.gluon.model_zoo.bert import BERTForPretraining
    from mxnet_tpu.parallel import scan_steps

    if on_cpu:
        # tiny model; keep bs distinct so grid rows stay distinguishable
        bs = max(2, bs // 16)
        seq, units, layers, heads, vocab = 32, 64, 2, 4, 1000
        k_steps = 2
    else:  # BERT-base: 12 layers, 768 units, 12 heads (BASELINE.json row 2)
        seq, units, layers, heads, vocab = 128, 768, 12, 12, 30522
    cdtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

    net = BERTForPretraining(vocab_size=vocab, units=units,
                             hidden_size=units * 4, num_layers=layers,
                             num_heads=heads, max_length=512,
                             dropout=dropout, embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((2, seq), dtype="int32"))
    trainable, aux = functional.split_params(net)
    opt_m = jax.tree_util.tree_map(jnp.zeros_like, trainable)
    n_params = sum(int(v.size) for v in trainable.values())

    def train_step(trainable, opt_m, ids, labels):
        def loss_fn(tr):
            from mxnet_tpu.ops.xent import sparse_softmax_xent
            (mlm, _nsp), _ = functional.functional_call(
                net, {**_cast_tree(tr, cdtype), **aux}, ids, train=True)
            return jnp.mean(sparse_softmax_xent(mlm, labels))
        loss, grads = jax.value_and_grad(loss_fn)(trainable)
        opt_m = jax.tree_util.tree_map(
            lambda m, g: 0.9 * m + g.astype(m.dtype), opt_m, grads)
        trainable = jax.tree_util.tree_map(
            lambda w, m: w - 1e-3 * m, trainable, opt_m)
        return trainable, opt_m, loss

    loop = scan_steps(train_step, n_state=2)
    step = jax.jit(loop, donate_argnums=(0, 1))
    ids = jnp.asarray(onp.random.randint(0, vocab, (k_steps, bs, seq)),
                      jnp.int32)
    step, cost = _compile(step, trainable, opt_m,
                          jax.ShapeDtypeStruct(ids.shape, ids.dtype),
                          jax.ShapeDtypeStruct(ids.shape, ids.dtype))
    sec, _ = _measure(step, (trainable, opt_m, ids, ids), n_state=2)
    sec /= k_steps
    flops = 6.0 * n_params * bs * seq   # 6ND training rule
    drop_tag = f"_drop{dropout}" if dropout else ""
    row = _row(f"bert_base_pretrain_bs{bs}_seq{seq}{drop_tag}_{precision}",
               sec, bs,
               flops, precision, peak, cost=cost)
    row["steps_per_call"] = k_steps
    row["config"] = _config_dict(bs, k_steps)
    row["params_m"] = round(n_params / 1e6, 1)
    from mxnet_tpu import config as _cfg
    row["fused_ln_residual"] = str(_cfg.get("fused_ln_residual"))
    return row


def bench_gpt_train(precision: str, on_cpu: bool, peak, bs=8, seq=1024,
                    k_steps=8):
    """Decoder-only LM pretraining step (gpt2-124m class).

    Causal attention routes through the Pallas flash kernel from seq 512
    up (ops/attention.py _FLASH_MIN_SEQ_CAUSAL — measured crossover on
    v5e) instead of materializing (s, s) scores in HBM, so BOTH grid rows
    (seq 1024 and 2048) are flash rows; the row difference is pure
    sequence-length scaling, and row['flash_attention'] says whether a
    Mosaic call is in the compiled step."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import functional
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM
    from mxnet_tpu.parallel import scan_steps

    if on_cpu:
        bs, seq, k_steps = 2, 32, 2
        units, layers, heads, vocab = 64, 2, 4, 1000
    else:  # GPT-2 small: 12 layers, 768 units, 12 heads
        units, layers, heads, vocab = 768, 12, 12, 50257
    cdtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

    net = GPTForCausalLM(vocab_size=vocab, units=units,
                         hidden_size=units * 4, num_layers=layers,
                         num_heads=heads, max_length=seq,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((2, seq), dtype="int32"))
    trainable, aux = functional.split_params(net)
    opt_m = jax.tree_util.tree_map(jnp.zeros_like, trainable)
    n_params = sum(int(v.size) for v in trainable.values())

    def train_step(trainable, opt_m, ids):
        def loss_fn(tr):
            from mxnet_tpu.ops.xent import sparse_softmax_xent
            logits, _ = functional.functional_call(
                net, {**_cast_tree(tr, cdtype), **aux}, ids[:, :-1],
                train=True)
            return jnp.mean(sparse_softmax_xent(logits, ids[:, 1:]))
        loss, grads = jax.value_and_grad(loss_fn)(trainable)
        opt_m = jax.tree_util.tree_map(
            lambda m, g: 0.9 * m + g.astype(m.dtype), opt_m, grads)
        trainable = jax.tree_util.tree_map(
            lambda w, m: w - 1e-3 * m, trainable, opt_m)
        return trainable, opt_m, loss

    loop = scan_steps(train_step, n_state=2)
    step = jax.jit(loop, donate_argnums=(0, 1))
    ids = jnp.asarray(onp.random.randint(0, vocab, (k_steps, bs, seq + 1)),
                      jnp.int32)
    step, cost = _compile(step, trainable, opt_m,
                          jax.ShapeDtypeStruct(ids.shape, ids.dtype))
    sec, _ = _measure(step, (trainable, opt_m, ids), n_state=2)
    sec /= k_steps
    flops = 6.0 * n_params * bs * seq  # 6ND training rule
    row = _row(f"gpt2_124m_pretrain_bs{bs}_seq{seq}_{precision}", sec, bs,
               flops, precision, peak, cost=cost)
    row["steps_per_call"] = k_steps
    row["config"] = _config_dict(bs, k_steps)
    row["params_m"] = round(n_params / 1e6, 1)
    # read off the executable that was timed, not inferred from seq
    row["flash_attention"] = "tpu_custom_call" in step.as_text()
    return row


def bench_gpt_decode_serve(precision, on_cpu, peak, slots=8, requests=24,
                           max_new=48, mode="base"):
    """Online decode through mx.serve continuous batching (gpt2-124m
    class on hardware, the CI tiny config on CPU): tokens/s plus the SLO
    latencies (TTFT/TPOT p50/p99) the serving row is judged by.
    precision='int8'/'int4' routes weights through the low-bit decode
    path (serve/quantize.py) — the bandwidth-bound regime where weight
    bytes are the roofline; int4 adds the int8 KV cache on top (the
    bytes-minimal decode config).  mode='prefix' serves a shared-prefix
    workload through the radix prefix cache (reports the hit rate);
    mode='spec' attaches a self-draft speculative decoder (reports the
    acceptance rate — a plumbing row, the TPOT story needs a cheaper
    draft)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.gpt import GPTForCausalLM

    if on_cpu:
        vocab, units, layers, heads, maxlen = 512, 64, 2, 4, 128
        requests, max_new, slots = 12, 24, 4
    else:  # GPT-2 small decode
        vocab, units, layers, heads, maxlen = 50257, 768, 12, 12, 512
    quantize = {"int8": "int8_weights",
                "int4": "int4_weights,int8_kv"}.get(precision)
    net = GPTForCausalLM(vocab_size=vocab, units=units,
                         hidden_size=units * 4, num_layers=layers,
                         num_heads=heads, max_length=maxlen,
                         dropout=0.0, embed_dropout=0.0)
    net.initialize()
    net(mx.np.zeros((1, 2), dtype="int32"))
    eng = mx.serve.load(
        net, max_slots=slots, quantize=quantize,
        prefix_cache=(mode == "prefix"),
        draft=(net if mode == "spec" else None),
        warmup=True)  # compile outside the timed window

    rng = onp.random.RandomState(0)
    shared = rng.randint(1, vocab, size=maxlen // 2).tolist()
    t0 = time.perf_counter()
    for _ in range(requests):
        if mode == "prefix":  # shared-prefix mix: the cache's workload
            prompt = shared + rng.randint(
                1, vocab, size=int(rng.randint(1, 9))).tolist()
        else:
            length = int(rng.randint(2, min(24, maxlen // 4) + 1))
            prompt = rng.randint(1, vocab, size=length).tolist()
        eng.submit(prompt, max_new_tokens=max_new)
    eng.run()
    wall = time.perf_counter() - t0
    st = eng.stats()
    suffix = "" if mode == "base" else f"_{mode}"
    row = {"name": f"gpt2_decode_serve_slots{slots}_{precision}{suffix}",
           "items_per_s": st["tokens_out"] / wall,
           "unit": "tokens/s",
           "ms_per_step": wall / max(1, st["steps"]) * 1e3,
           "precision": precision,
           "requests": requests,
           "ttft_p50_ms": (st["ttft"]["p50"] or 0) * 1e3,
           "ttft_p99_ms": (st["ttft"]["p99"] or 0) * 1e3,
           "tpot_p50_ms": (st["tpot"]["p50"] or 0) * 1e3,
           "tpot_p99_ms": (st["tpot"]["p99"] or 0) * 1e3,
           "post_warmup_compiles": st["post_warmup_compiles"]}
    if mode == "prefix":
        row["prefix_hit_rate"] = st["prefix"]["hit_rate"]
        row["prefix_tokens_reused"] = st["prefix"]["tokens_reused"]
    elif mode == "spec":
        row["spec_acceptance_rate"] = st["spec"]["acceptance_rate"]
        row["spec_rounds"] = st["spec"]["rounds"]
    if quantize:
        row["weight_bytes_ratio"] = round(
            st["weight_bytes"] / st["weight_bytes_fp"], 3)
        row["quant_config"] = {
            "quantize": st["quantize"], "cache_dtype": st["cache_dtype"],
            "quantized_params": st["quantized_params"],
            "passthrough_params": st["passthrough_params"]}
    return row


def bench_augmentation(precision, on_cpu, peak, bs=256, k_steps=8):
    """Batched image-augmentation throughput (mx.image.apply_batch):
    the ImageIter/DataLoader device-side augment pass."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import image as mimg

    if on_cpu:
        bs, k_steps = 16, 2
    chain = mimg.CreateAugmenter((3, 224, 224), rand_crop=True,
                                 rand_resize=True, rand_mirror=True,
                                 brightness=0.4, contrast=0.4,
                                 saturation=0.4, pca_noise=0.1,
                                 mean=True, std=True)

    def aug_step(carry, key, xs):
        def body(c, x):
            out = mimg.apply_batch(chain, x + c, key=key)._data
            return jnp.max(out).astype(jnp.float32), None
        c, _ = jax.lax.scan(body, carry, xs)
        return c, c

    key = jax.random.PRNGKey(0)
    xs = jax.random.uniform(key, (k_steps, bs, 256, 256, 3),
                            jnp.float32, 0, 255)
    step = jax.jit(aug_step)
    step, _ = _compile(step, jax.ShapeDtypeStruct((), jnp.float32),
                       jax.ShapeDtypeStruct(key.shape, key.dtype),
                       jax.ShapeDtypeStruct(xs.shape, xs.dtype))
    sec, _ = _measure(step, (jnp.zeros(()), key, xs), n_state=1)
    sec /= k_steps
    return {"name": f"augment_imagenet_bs{bs}", "items_per_s": bs / sec,
            "ms_per_step": sec * 1e3, "precision": "fp32"}


def bench_dataloader_workers(precision, on_cpu, peak, n=256, dim=2048,
                             workers=4):
    """Python-transform DataLoader: thread pool vs spawn process pool.

    The transform is pure-python CPU work (the GIL wall the reference's
    multiprocess workers exist for, gluon/data/dataloader.py:28-187);
    reports process-pool throughput with the thread-pool number alongside.
    """
    import time as _t

    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataloader import _PyBenchDataset

    if on_cpu:
        # 1-core fallback boxes: spawn-pool warmup dominates; shrink hard
        # so the row cannot push the whole bench past the driver timeout
        n, workers = 32, 2
    ds = _PyBenchDataset(n, dim)

    def run(thread_pool):
        dl = DataLoader(ds, batch_size=16, num_workers=workers,
                        thread_pool=thread_pool)
        for _warm in range(1 if thread_pool else 3):
            for b in dl:  # warm pool (spawn workers boot lazily) + caches
                pass
        t0 = _t.time()
        cnt = 0
        for b in dl:
            cnt += b.shape[0]
        sec = _t.time() - t0
        if not thread_pool:
            dl._proc_pool.shutdown(wait=False, cancel_futures=True)
        return cnt / sec

    thr = run(True)
    proc = run(False)
    return {"name": f"dataloader_pytransform_w{workers}",
            "items_per_s": proc, "thread_items_per_s": thr,
            "proc_vs_thread": proc / thr, "precision": "fp32",
            "ms_per_step": 16e3 / proc}


def _device():
    """The device the grid runs on.  No accelerator and no explicit
    JAX_PLATFORMS=cpu is an error: a CPU timing must never land under a
    chip metric's name because the chip was missing."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform 'cpu'). Set "
            "JAX_PLATFORMS=cpu to ask for the toy-shape CPU smoke.")
    return dev


_TRAIN_FAMILIES = {
    "resnet50_train": "bench_resnet50_train",
    "bert_train": "bench_bert_train",
    "gpt_train": "bench_gpt_train",
}


def _tuned_entries(path):
    """Turn an autotune winners file (mx.autotune winners.json, or a plain
    {workload: config} mapping) into extra tuned grid points.

    Each tuned config feeds its batch/steps_per_call into the train-family
    benches; the winner's full config rides on the row as "tuned_config"
    (the hand-rolled bench steps run zero=0/grad_accum=1/remat=off, and
    row["config"] always records what actually executed)."""
    with open(path) as f:
        data = json.load(f)
    g = globals()
    entries = []
    if isinstance(data, dict) and "winners" in data:
        # one tuned point per distinct winner config, across all train
        # families (the winners file has no workload names — keys are
        # model-fingerprint based)
        seen = set()
        for rec in data["winners"].values():
            cfg = rec.get("config", {})
            key = json.dumps(cfg, sort_keys=True)
            if key in seen or "batch_size" not in cfg:
                continue
            seen.add(key)
            for fn_name in _TRAIN_FAMILIES.values():
                entries.append((g[fn_name],
                                dict(precision="bf16", bs=cfg["batch_size"],
                                     k_steps=cfg.get("steps_per_call"),
                                     _tuned=cfg)))
    elif isinstance(data, dict):
        for workload, cfg in data.items():
            fn_name = _TRAIN_FAMILIES.get(workload, workload)
            if fn_name not in g:
                raise SystemExit(f"--config: unknown workload {workload!r}")
            entries.append((g[fn_name],
                            dict(precision="bf16", bs=cfg["batch_size"],
                                 k_steps=cfg.get("steps_per_call"),
                                 _tuned=cfg)))
    return entries


_GRID = [
    (bench_resnet50_train, dict(precision="bf16")),   # headline (bs32)
    (bench_resnet50_train, dict(precision="bf16", bs=64)),
    (bench_resnet50_train, dict(precision="bf16", bs=128)),
    (bench_resnet50_train, dict(precision="bf16", bs=256)),
    (bench_resnet50_train, dict(precision="fp32")),
    (bench_resnet50_infer, dict(precision="bf16", bs=1)),
    (bench_resnet50_infer, dict(precision="bf16")),   # bs32
    (bench_resnet50_infer, dict(precision="bf16", bs=128)),
    (bench_resnet50_infer, dict(precision="int8")),
    (bench_inception_train, dict(precision="bf16")),  # bs32
    (bench_inception_train, dict(precision="bf16", bs=64)),
    (bench_bert_train, dict(precision="bf16", bs=32)),
    (bench_bert_train, dict(precision="bf16", bs=48)),
    (bench_bert_train, dict(precision="bf16", bs=64)),
    (bench_gpt_train, dict(precision="bf16", bs=8, seq=1024)),
    (bench_gpt_train, dict(precision="bf16", bs=4, seq=2048)),
    (bench_gpt_decode_serve, dict(precision="fp32")),
    (bench_gpt_decode_serve, dict(precision="fp32", mode="prefix")),
    (bench_gpt_decode_serve, dict(precision="fp32", mode="spec")),
    (bench_gpt_decode_serve, dict(precision="int8")),
    (bench_gpt_decode_serve, dict(precision="int4")),
    (bench_augmentation, dict(precision="fp32")),
    (bench_dataloader_workers, dict(precision="fp32")),
]


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description="mxnet_tpu benchmark grid")
    ap.add_argument("--config", default=None, metavar="WINNERS_JSON",
                    help="autotune winners file; each tuned config is "
                         "added to the grid as extra train-family rows")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write the summary JSON to this file; "
                         "stdout's final line is always the JSON alone")
    args = ap.parse_args(argv)

    from mxnet_tpu import _compile_cache
    from mxnet_tpu import insight as _insight
    dev = _device()
    on_cpu = dev.platform == "cpu"
    if not on_cpu:
        # the requested CPU smoke stays cache-free like the test suite
        _compile_cache.configure(_compile_cache.CHECKOUT_CACHE)
    # no MFU on the CPU: its nominal peak row is for CI gauges, not speed
    peak = None if on_cpu else _insight.peaks()[0]

    # arm the goodput ledger for the grid so every row reports its
    # goodput_fraction + top badput causes (reset per row below)
    from mxnet_tpu import goodput as _goodput
    _goodput.enable()

    rows = []
    grid = _GRID + (_tuned_entries(args.config) if args.config else [])
    current = None
    try:
        for fn, kwargs in grid:
            kwargs = dict(kwargs)
            tuned = kwargs.pop("_tuned", None)
            if kwargs.get("k_steps") is None:
                kwargs.pop("k_steps", None)
            if tuned is None and on_cpu and kwargs.get("bs", 32) != 32 \
                    and fn in (bench_resnet50_train, bench_resnet50_infer,
                               bench_inception_train):
                # the requested CPU smoke shrinks every CNN row to one
                # tiny config — the batch-size rows would be duplicates
                continue
            if tuned is None and on_cpu and fn is bench_gpt_train \
                    and kwargs.get("seq") != 1024:
                continue  # same dedup for the shrunken GPT rows
            if _goodput._active:
                _goodput.reset()   # per-row ledger window
            current = f"{fn.__name__}{kwargs}"
            row = fn(on_cpu=on_cpu, peak=peak, **kwargs)
            if tuned is not None:
                row["tuned"] = True
                row["tuned_config"] = tuned
            if "_train" in fn.__name__ or "_decode" in fn.__name__:
                # the Pallas block shapes this row executed with (static
                # defaults unless kernel winners are loaded) — makes a
                # tuned vs untuned A/B readable straight off the bench JSON
                from mxnet_tpu import autotune as _at
                row["kernel_config"] = _at.kernel_config_summary()
            rows.append({k: (round(v, 2) if isinstance(v, float) else v)
                         for k, v in row.items()})
        current = None
    finally:
        # a row that raised ends the run (the traceback follows on
        # stderr, the exit code is non-zero); what was measured before it
        # is still printed, with the failed row named
        if current is not None:
            rows.append({"name": current,
                         "error": repr(sys.exc_info()[1])})
        _emit(rows, dev, peak, args.out)


def _emit(rows, dev, peak, out):
    import jax
    head = next((r for r in rows if "items_per_s" in r), {})
    best_mfu = max((r["mfu"] for r in rows
                    if "mfu" in r and r.get("valid", True)), default=None)
    summary = json.dumps({
        "metric": head.get("name", "resnet50_train"),
        "value": head.get("items_per_s"),
        "unit": "images/sec",
        "vs_baseline": (round(head["items_per_s"] / BASELINE_TRAIN_IMG_S, 3)
                        if head.get("items_per_s") else None),
        "mfu": head.get("mfu"),
        "best_mfu": best_mfu,
        "precision": head.get("precision"),
        "ms_per_step": head.get("ms_per_step"),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "chip_peak_bf16_tflops": round(peak / 1e12, 1) if peak else None,
        "grid": rows,
    })
    if out:
        with open(out, "w") as f:
            f.write(summary + "\n")
    print(summary, flush=True)


if __name__ == "__main__":
    main()
