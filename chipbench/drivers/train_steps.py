"""Training traffic: a user's loop around ``ShardedTrainStep``.

The mix's file gives the token batch of one update (``sequences`` x
``seq_len``), how often the loop looks at the loss (``fetch_every``)
and how long the traced part of a traced window is.  The cell's file
gives the step's own arguments (mesh, zero, grad_accum, remat,
precision), AMP and the optimizer.

Set-up builds ONE object — the compiled step with its state — from the
seed, drives it through its first ``check.steps`` updates (which the
plain reference follows after the window) and hands that same object to
the window.  In the window each update gets a fresh batch made on the
host from the seed; the loss is fetched every ``fetch_every``-th update
and once more at the end, and that last fetch closes the window, so the
rate is over all the work and all the time.
"""
from __future__ import annotations

import gc
import time

import numpy as onp

from common import Check, memory_peak_bytes, span
from drivers_trace import start_trace, stop_trace


def batches(seed, vocab, sequences, seq_len):
    """An endless stream of (inputs, labels): rows of uniform tokens,
    labels the inputs shifted by one."""
    rng = onp.random.default_rng(seed)
    while True:
        t = rng.integers(0, vocab, (sequences, seq_len + 1), dtype=onp.int32)
        yield t[:, :-1], t[:, 1:]


def build(ctx):
    """The program side: AMP, net with seeded weights, the sharded step."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import MeshConfig, ShardedTrainStep

    cell, cfg, family = ctx["cell"], ctx["cfg"], ctx["family"]
    if cell.get("amp"):
        mx.amp.init(cell["amp"])
    with span("setup.weights"):
        net = family.build_net(
            cfg, family.make_weights(cfg, ctx["seed"], "float32"))
    st = cell["step"]
    o = cell["optimizer"]
    mesh = MeshConfig(**st["mesh"])
    with span("setup.step"):
        train = ShardedTrainStep(
            net, family.loss_fn,
            mx.optimizer.create(o["name"], learning_rate=o["lr"],
                                beta1=o["beta1"], beta2=o["beta2"],
                                epsilon=o["epsilon"]),
            mesh, batch_specs=mesh.batch_specs(2, 2), n_labels=1,
            zero=st["zero"], grad_accum=st["grad_accum"],
            remat=st["remat"], precision=st["precision"],
            grad_compress=st.get("grad_compress", "none"))
    return net, train


def feed(train, x, y):
    """One update through the step's own call; with grad_accum K the
    batch gains the leading K axis the step asks for."""
    k = train.grad_accum
    if k > 1:
        x = x.reshape(k, x.shape[0] // k, x.shape[1])
        y = y.reshape(k, y.shape[0] // k, y.shape[1])
    return train(x, y)


def first_grad_norms(train, beta1):
    """Per-leaf norm of the first gradient as the optimizer got it:
    Adam's first moment after one update is (1 - beta1) * g."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(states):
        return {n: jnp.sqrt(jnp.sum(jnp.square(s[0].astype(jnp.float32))))
                / (1.0 - beta1) for n, s in states.items()}

    return jax.device_get(norms(train.states))


def run(ctx):
    import jax

    cell, cfg, mix = ctx["cell"], ctx["cfg"], ctx["traffic"]
    family, watch = ctx["family"], ctx["watch"]
    seconds = ctx["seconds"]
    n_seq, seq = mix["sequences"], mix["seq_len"]
    check_steps = cell["check"]["steps"]

    net, train = build(ctx)
    stream = batches(ctx["seed"], cfg["vocab_size"], n_seq, seq)

    # ---- the first updates: compiled here, checked after the window ---
    first, losses, grad_norms = [], [], None
    for i in range(check_steps):
        x, y = next(stream)
        first.append((x, y))
        with span("setup.first_steps"):
            losses.append(float(feed(train, x, y).asnumpy()))
        if i == 0:
            grad_norms = first_grad_norms(train, cell["optimizer"]["beta1"])
    change_norms = jax.device_get(
        family.change_norms(cfg, ctx["seed"], train.trainable))
    setup = watch.snapshot()

    # ---- the window ----------------------------------------------------
    trace_at = seconds - min(mix["trace_seconds"], seconds) \
        if ctx["trace"] else None
    tracing = None
    fetches = []            # (host time, updates completed by then)
    steps = 0
    t_open = time.perf_counter()
    setup_s = t_open - ctx["t_process"]
    fetches.append((t_open, 0))
    while True:
        with span("train.batch"):
            x, y = next(stream)
        with span("train.step"):
            loss = feed(train, x, y)
        steps += 1
        now = time.perf_counter()
        if now - t_open >= seconds:
            break
        if trace_at is not None and tracing is None \
                and now - t_open >= trace_at:
            tracing = start_trace(ctx)
        if steps % mix["fetch_every"] == 0:
            with span("train.fetch"):
                last_loss = float(loss.asnumpy())
            fetches.append((time.perf_counter(), steps))
    with span("train.fetch"):
        last_loss = float(loss.asnumpy())
    t_close = time.perf_counter()
    fetches.append((t_close, steps))
    xplane = stop_trace(ctx, tracing) if tracing is not None else None
    t_traced = time.perf_counter()
    in_window = watch.snapshot()
    peak = memory_peak_bytes(ctx["devices"])

    window_s = t_close - t_open
    tokens = steps * n_seq * seq
    flops_per_token = ctx["flops"].train_flops_per_token(cfg, seq)
    mfu = 100.0 * tokens * flops_per_token / window_s \
        / (ctx["chips"] * ctx["peak"]["bf16_flops"])
    print(f"# train: {steps} updates of {n_seq} x {seq} tokens in "
          f"{window_s:.3f} s = {tokens / window_s:.1f} tokens/s, "
          f"{1e3 * window_s / steps:.2f} ms/update, last loss "
          f"{last_loss:.4f}", flush=True)

    # ---- the check, outside the window, after the program is freed ----
    del net, train, loss
    gc.collect()
    opt = dict(cell["optimizer"])
    t_ref = time.perf_counter()
    ref = ctx["reference"].train_reference(
        lambda: family.make_weights(cfg, ctx["seed"], "float32"), first,
        cfg, opt, devices=ctx["devices"])
    reference_s = time.perf_counter() - t_ref
    lim = cell["check"]["limits"]
    check = Check()
    for i, (a, b) in enumerate(zip(losses, ref["losses"]), start=1):
        check.at_most(f"loss_gap_step{i}", abs(a - b), limit=lim["loss_gap"])
    stacked = family.stack_program_tree
    L = cfg["n_layer"]
    reference = ctx["reference"]
    g_gaps = reference.leaf_gaps(stacked(grad_norms, L), ref["grad_norms"])
    c_gaps = reference.leaf_gaps(stacked(change_norms, L),
                                 ref["change_norms"])
    # a leaf whose true gradient is zero (GPT-2's key bias: softmax does
    # not see it) gets Adam's full-size steps from rounding noise on both
    # sides; its change says nothing of the optimizer
    dead = reference.dead_leaves(ref["grad_norms"])
    check.at_most("grad_norm_gap_worst_leaf", *reference.worst_leaf(g_gaps),
                  limit=lim["grad_norm_gap"])
    check.at_most("change_norm_gap_worst_live_leaf",
                  *reference.worst_leaf(c_gaps, skip=dead),
                  limit=lim["change_norm_gap"])
    check.exactly("programs_compiled_in_window",
                  in_window["programs"] - setup["programs"], 0)

    return {
        "end_to_end": {"train_mfu": mfu, "setup_s": setup_s},
        "attempted": steps, "failed": 0,
        "check": check, "memory_peak_bytes": peak, "xplane": xplane,
        "info": {"setup_s": setup_s, "compile_setup": setup,
                 "losses": losses, "reference_losses": ref["losses"],
                 "last_loss": last_loss, "updates": steps,
                 "tokens_per_s": tokens / window_s, "window_s": window_s,
                 "stop_trace_s": t_traced - t_close,
                 "reference_s": reference_s},
        "observations": {
            "fetches": fetches, "updates": steps,
            "window": (t_open, t_close), "memory_peak_bytes": peak,
            "compile_setup": setup, "tokens_per_update": n_seq * seq,
            "sequences": n_seq, "seq_len": seq,
        },
    }
