"""A second witness for the generator's setting (PERF.md section 6, PR 32).

At the generator's first setting — embedding rows at N(0, 0.02), what the
layout's other leaves get — the program under AMP and the float32
reference parted ways layer by layer on the chip, and the rows went to
N(0, 0.02 x sqrt(hidden)).  Rounding that a seeded network amplifies, or
a fault of the program's that the larger rows hide?  This runs the cell
through its own driver and its own check at the cell's sizes with the
rows put back (``--rows 1`` = N(0, 0.02)) and the program's rounding
taken away: AMP off, every product at ``highest``.  The flash kernels,
the indexer, the top-k and the expert layer are the ones the cell times.
If program and reference now agree as float32 does, the departure was
rounding; if not, it is the program.  ``--amp`` keeps AMP on, for the
departure itself beside it.

    python chipbench/dev/keye_witness.py --seed <seed> [--rows 1]
        [--out 0.1] [--amp | --self] [--cpu [--seq 2048]]

``--self`` runs nothing of the program: the float32 reference follows the
cell's updates twice, on the generator's weights and on the same weights
moved one float32 ulp up, and the check's rows are read between the two.
That is the least any second float32 implementation can read at that
setting: where it is past the cell's limits, the setting and not an
implementation is what parts them.

``--out`` scales attention's output projection: the first chip round ran
rows x 1 with it at 0.1 (at 1 the loads are so uneven that the program's
``rows_bound`` overflows, which parts program and reference by
construction, whatever the precision).

On the chip that step is 13.83 + 1.86 = 15.69 of 16.9 GB (a compile for
a described v5e; the kernels' products lower with Mosaic's float32
contract precision).  ``--cpu`` is the same run where every product is
float32 by nature: the cell's sizes through the XLA composition (no
kernels; ~80 GB of host memory and hours of eight cores).  ``--seq``
shortens the sequence there, with ``topk`` a quarter of it and
``rows_bound`` twice it as at 8192, every width kept: minutes, not hours.

The lines are ``chipbench/run.py``'s (``# check``, ``# counts``, the
result line); no time in them means anything.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

CELL = "keye-train-8k"

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=float, default=1.0,
                    help="embedding rows, in units of N(0, 0.02)")
    ap.add_argument("--out", type=float, default=1.0,
                    help="attention's output projection, on top of "
                         "N(0, 0.02) / sqrt(2 x layers)")
    ap.add_argument("--amp", action="store_true")
    ap.add_argument("--self", dest="self_", action="store_true",
                    help="the reference against itself, one ulp apart")
    ap.add_argument("--tiny", action="store_true",
                    help="the CPU rehearsal's sizes")
    ap.add_argument("--cpu", action="store_true",
                    help="the cell's sizes off the chip")
    ap.add_argument("--seq", type=int, default=None,
                    help="a shorter sequence (topk = seq / 4, rows_bound "
                         "= 2 x seq), with --cpu")
    args = ap.parse_args()

    import jax
    if not args.amp:
        jax.config.update("jax_default_matmul_precision", "highest")

    resolve, load_module = run.resolve, run.load_module

    def resolve_without_amp(bench, workload, tiny=False, control=False):
        # off the chip ``run.main`` only rehearses: let it, at real sizes
        entry, cell, cfg, traffic = resolve(bench, workload,
                                            tiny and not args.cpu, control)
        if args.seq:
            traffic = dict(traffic, seq_len=args.seq)
            cfg = dict(cfg, rows_bound=2 * args.seq, sa_config=dict(
                cfg["sa_config"], topk=args.seq // 4))
        return entry, cell if args.amp else dict(cell, amp=None), cfg, traffic

    def load_with_rows(kind, name):
        mod = load_module(kind, name)
        if kind == "families":
            _, _, cfg, _ = resolve(run.load_json(run.ROOT, "BENCHMARK.json"),
                                   CELL, args.tiny and not args.cpu)
            mod.EMBED_SCALE = args.rows / cfg["hidden_size"] ** 0.5
            make = mod._make_leaf

            def make_scaled(key, index, name, *rest):
                w = make(key, index, name, *rest)
                return w * args.out if name == "attn.o.w" else w

            mod._make_leaf = make_scaled
        return mod

    run.resolve, run.load_module = resolve_without_amp, load_with_rows

    def reference_against_itself():
        import json
        import jax.numpy as jnp
        sys.path.insert(0, run.ROOT)
        _, cell, cfg, mix = run.resolve(
            run.load_json(run.ROOT, "BENCHMARK.json"), CELL, args.tiny)
        family = run.load_module("families", cfg["family"])
        reference = run.load_module("reference", cfg["family"])
        stream = run.load_module("drivers", mix["driver"]).batches(
            args.seed, cfg["vocab_size"], mix["sequences"], mix["seq_len"])
        first = [next(stream) for _ in range(cell["check"]["steps"])]

        def made():
            return family.make_weights(cfg, args.seed, "float32")

        def one_ulp_up():
            return {n: jnp.nextafter(w, jnp.inf) for n, w in made().items()}

        a, b = (reference.train_reference(make, first, cfg,
                                          dict(cell["optimizer"]))
                for make in (made, one_ulp_up))
        rows = {f"loss_gap_step{i}": abs(x - y) for i, (x, y) in
                enumerate(zip(a["losses"], b["losses"]), start=1)}
        rows["grad_norm_gap_worst_leaf"] = reference.worst_leaf(
            reference.leaf_gaps(b["grad_norms"], a["grad_norms"]))
        rows["change_norm_gap_worst_live_leaf"] = reference.worst_leaf(
            reference.leaf_gaps(b["change_norms"], a["change_norms"]),
            skip=reference.dead_leaves(a["grad_norms"]))
        print("# self " + json.dumps(rows), flush=True)
    print(f"# witness: rows x {args.rows}, attn.o.w x {args.out}, "
          + ("the reference against itself one ulp up" if args.self_ else
             "AMP bf16" if args.amp else "AMP off, products at highest")
          + (f", seq {args.seq}" if args.seq else ""), flush=True)
    if args.self_:
        reference_against_itself()
        sys.exit()
    run.main(["--workload", CELL, "--seed", str(args.seed), "--seconds", "2"]
             + ["--tiny"] * (args.tiny or args.cpu))
