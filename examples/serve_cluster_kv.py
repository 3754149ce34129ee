"""Clustered-KV long-context decode: the paper's seeder as a serving feature.

    PYTHONPATH=src python examples/serve_cluster_kv.py [--seq 16384] [--engine]

Builds a synthetic long KV cache, clusters the keys per head with
FASTK-MEANS++ (+Lloyd), and compares clustered two-level attention against
exact full attention: output error, attention-mass recall, and the
bytes-read reduction that drives the memory-roofline win (EXPERIMENTS.md
§Perf, cell qwen3-32b x long-context).

`--engine` serves the per-head codebook rebuilds through the async
`ClusterEngine` pipeline (docs/architecture.md): while one head's codebook
solves on device, the next head's embedding/prepare runs on the host
thread pool — the rebuild pattern of a live serving loop, bit-identical
to the serial build.
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--clusters", type=int, default=256)
    ap.add_argument("--topc", type=int, default=24)
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--engine", action="store_true",
                    help="pipeline the per-head codebook rebuilds through "
                         "ClusterEngine (overlap host prepare with device "
                         "solve; bit-identical results)")
    args = ap.parse_args()

    import jax.numpy as jnp

    from repro.models.cluster_attn import (
        ClusterKVConfig,
        build_clustered_cache,
        clustered_attention,
    )

    rng = np.random.default_rng(0)
    b, s, hk, dh = 1, args.seq, args.heads, args.head_dim
    # keys with topical structure (mixture) — the realistic regime
    topics = rng.normal(size=(48, dh)) * 2.0
    keys = (topics[rng.integers(48, size=(b, s))][:, :, None, :]
            + rng.normal(size=(b, s, 1, dh)) * 0.7).repeat(hk, axis=2)
    keys = keys.astype(np.float32)
    values = rng.normal(size=(b, s, hk, dh)).astype(np.float32)

    cfg = ClusterKVConfig(num_clusters=args.clusters, topc=args.topc,
                          lloyd_iters=2, capacity_slack=3.0)
    t0 = time.time()
    info = {}
    if args.engine:
        from repro.core import ClusterEngine
        from repro.core.plan import SOLVE_SPAN
        from repro.core.tracing import span_totals

        # Every head is a fresh dataset submitted exactly once:
        # retain_prepared=False keeps the prepare cache at pipeline depth
        # instead of accumulating all heads' artifacts until close.
        dispatch0 = span_totals().get(SOLVE_SPAN, {}).get("seconds", 0.0)
        with ClusterEngine(retain_prepared=False) as engine:
            cache = build_clustered_cache(keys, values, cfg, info=info,
                                          engine=engine)
            st = engine.stats()
        print(f"codebook rebuild via ClusterEngine x {hk} heads: "
              f"{time.time()-t0:.1f}s wall "
              f"(host prepare {st['prepare_seconds']:.1f}s overlapped with "
              f"solve dispatch "
              f"{st['spans'][SOLVE_SPAN]['seconds'] - dispatch0:.1f}s; "
              f"capacity-dropped tokens: {100*info['dropped_frac']:.2f}%)")
    else:
        cache = build_clustered_cache(keys, values, cfg, info=info)
        print(f"codebook build (fastkmeans++ x {hk} heads): "
              f"{time.time()-t0:.1f}s; "
              f"capacity-dropped tokens: {100*info['dropped_frac']:.2f}%")

    scale = 1.0 / np.sqrt(dh)
    kf = keys.transpose(0, 2, 1, 3)          # (B, Hk, S, Dh)
    vf = values.transpose(0, 2, 1, 3)
    errs, coverages = [], []
    for _ in range(args.queries):
        # queries aligned with a topic (real attention is concentrated;
        # uniform attention is the worst case for ANY top-k method)
        qv = topics[rng.integers(48)] * 1.5 + rng.normal(size=dh) * 0.5
        q = jnp.asarray(np.broadcast_to(qv, (b, hk, dh)), jnp.float32)
        out_c = clustered_attention(q, cache, cfg, scale=scale)
        sc = np.einsum("bhd,bhsd->bhs", np.asarray(q), kf) * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out_e = np.einsum("bhs,bhsv->bhv", p, vf)
        err = np.abs(np.asarray(out_c) - out_e).max() / np.abs(out_e).max()
        errs.append(err)
        # exact attention mass covered by the gathered clusters
        cent = np.asarray(cache["centroids"][0])          # (Hk, C, Dh)
        csc = np.einsum("hd,hcd->hc", np.asarray(q)[0] * scale, cent)
        top = np.argsort(csc, axis=-1)[:, -cfg.topc:]      # (Hk, topc)
        # token -> cluster assignment from the slot layout
        from repro.core.lloyd import assign as _assign
        for h in range(hk):
            tok_cl, _ = _assign(keys[0, :, h, :].astype(np.float64),
                                cent[h].astype(np.float64))
            covered = np.isin(tok_cl, top[h])
            coverages.append(float(p[0, h][covered].sum()))
    kv_bytes_full = s * dh * 4 * 2
    cap = cache["k_slots"].shape[3]
    kv_bytes_clustered = (args.clusters + args.topc * cap) * dh * 4 * 2
    print(f"clustered vs exact attention over {args.queries} queries:")
    print(f"  max relative output error: {np.max(errs):.3f} "
          f"(median {np.median(errs):.3f})")
    print(f"  exact attention mass covered by gathered clusters: "
          f"{np.mean(coverages):.3f}")
    print(f"  KV bytes touched per decode step: full={kv_bytes_full/1e6:.1f}MB"
          f" clustered={kv_bytes_clustered/1e6:.2f}MB"
          f" ({kv_bytes_full/kv_bytes_clustered:.1f}x fewer)")


if __name__ == "__main__":
    main()
