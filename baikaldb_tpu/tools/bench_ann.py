"""ANN speedup benchmark: IVF candidates + exact re-rank vs brute force.

VERDICT r05 item #4 acceptance: recall@10 >= 0.95 vs exact on 1M x 128-d
with >5x speedup on CPU.  Prints ONE JSON line.

Run: python -m baikaldb_tpu.tools.bench_ann [--rows 1000000] [--dim 128]
     [--queries 32] [--k 10]
Runs on the backend the process has; the JSON line names it.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--nprobe", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ..ops.vector import (brute_force_topk, ivf_search_host, kmeans,
                              pack_ivf)

    rng = np.random.RandomState(42)
    nc = max(64, int(np.sqrt(args.rows)))
    centers = rng.randn(nc, args.dim).astype(np.float32) * 4
    base = (centers[rng.randint(0, nc, args.rows)]
            + rng.randn(args.rows, args.dim).astype(np.float32) * 0.4)
    queries = (base[rng.randint(0, args.rows, args.queries)]
               + rng.randn(args.queries, args.dim).astype(np.float32) * 0.1)

    t0 = time.time()
    cent, assign = kmeans(base, nc, iters=6)
    train_s = time.time() - t0
    order, starts, counts, max_count = pack_ivf(base, assign,
                                                n_clusters=len(cent))

    qd = jnp.asarray(queries)
    bd = jnp.asarray(base)
    base_sorted = base[order]

    def timed(fn, reps=3):
        jax.block_until_ready(fn())            # compile / warm caches
        t0 = time.time()
        for _ in range(reps):
            out = fn()
            jax.block_until_ready(out)         # accepts any pytree
        return (time.time() - t0) / reps, out

    # per-QUERY timing on both sides: the SQL plane serves one SELECT at a
    # time, so batch-amortized exact numbers would overstate brute force
    def run_exact():
        return [brute_force_topk(qd[i:i + 1], bd, None, args.k, "l2",
                                 "f32") for i in range(args.queries)]

    def run_ivf():
        return [ivf_search_host(queries[i], base_sorted, None, cent,
                                starts, counts, args.k, args.nprobe, "l2",
                                norms_sorted=norms)
                for i in range(args.queries)]

    norms = (base_sorted * base_sorted).sum(1)
    exact_s, exact_out = timed(run_exact)
    ivf_s, ivf_out = timed(run_ivf)
    ei = np.stack([np.asarray(i)[0] for _s, i in exact_out])
    vi = [order[p] for _s, p in ivf_out]
    recall = float(np.mean([
        len(set(ei[i]) & set(vi[i])) / min(args.k, len(vi[i]))
        for i in range(args.queries)]))
    print(json.dumps({
        "metric": f"ANN IVF speedup ({args.rows}x{args.dim}, k={args.k}, "
                  f"nprobe={args.nprobe})",
        "value": round(exact_s / ivf_s, 2), "unit": "x vs exact",
        "recall_at_k": round(recall, 4),
        "exact_ms": round(exact_s * 1e3, 1),
        "ivf_ms": round(ivf_s * 1e3, 1),
        "train_s": round(train_s, 1),
        "platform": jax.devices()[0].platform,
    }))


if __name__ == "__main__":
    main()
