"""The main-path Pallas kernels at a cell's widths against float64.

`kernel_errors` runs each kernel once on the cell's own point set, with
the cell's k, tree heights H, LSH tables L and largest candidate block B,
and returns the largest error of each output against a float64 NumPy
reference.  The distance kernels expand ``|x|^2 - 2 x.c + |c|^2`` in
float32, whose rounding scales with ``|x|^2 + |c|^2`` and not with the
distance: their errors are relative to that scale.  The others are
relative to the reference value.  A float32 kernel reads ~1e-7; one pass
of bfloat16 (8 mantissa bits) reads ~1e-3.

At most `KERNEL_ROWS` rows are checked.  A larger set is checked on a
sorted sample of that many rows, drawn from a stream of the run's seed
that nothing else draws from, at the cell's widths (d, k, H, L, B).  The
number of rows is not a width: the sharded program calls these kernels on
one shard at a time, so a sample no larger than a shard checks what the
window ran, and the check's float32 copy of the rows (lane-padded on the
chip) stays at about half a GB on the first chip whatever the cell's n.
A set of at most `KERNEL_ROWS` rows is checked whole, and `seed`'s own
stream is drawn from in the same order either way.

`ops` is the module whose kernels are checked: the program's
`repro.kernels.ops`, or a stand-in (the precision control).
"""

from __future__ import annotations

import numpy as np

__all__ = ["kernel_errors", "KERNEL_ROWS", "LSH_MISS_FLOOR"]

LSH_MISS_FLOOR = 1.0e30      # a kernel output at or above this is a miss
KERNEL_ROWS = 2 ** 20        # most rows checked (see the module docstring)
ROW_STREAM = 6               # the seed's stream of the row sample


def _d2_f64(x, c, chunk=32768):
    """Yields (rows, (rows, k) float64 squared distances) chunk by chunk."""
    c_sq = (c * c).sum(1)
    for lo in range(0, len(x), chunk):
        xs = x[lo:lo + chunk]
        d2 = (xs * xs).sum(1)[:, None] - 2.0 * xs @ c.T + c_sq[None, :]
        yield slice(lo, lo + len(xs)), np.maximum(d2, 0.0)


def kernel_errors(ops, x64: np.ndarray, *, k: int, h: int, l: int, b: int,
                  seed: int) -> dict:
    """Largest error of each kernel output (see the module docstring)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    if len(x64) > KERNEL_ROWS:
        sample = np.random.default_rng([int(seed), ROW_STREAM]).choice(
            len(x64), KERNEL_ROWS, replace=False)
        x64 = x64[np.sort(sample)]
    n, d = x64.shape
    x = jnp.asarray(x64, jnp.float32)
    xf = np.asarray(x, np.float64)          # the inputs the chip sees
    x_sq = (xf * xf).sum(1)
    errs = {}

    # pairwise_argmin: min and argmin over k centers.
    ci = rng.choice(n, k, replace=False)
    cf = xf[ci]
    scale = x_sq + (cf * cf).sum(1).max()   # |x|^2 + |c|^2 bound per row
    d2, idx = jax.block_until_ready(ops.pairwise_argmin(x, x[ci]))
    d2, idx = np.asarray(d2, np.float64), np.asarray(idx)
    e_min = e_arg = 0.0
    for rows, ref in _d2_f64(xf, cf):
        s = scale[rows]
        best = ref.min(1)
        e_min = max(e_min, float((np.abs(d2[rows] - best) / s).max()))
        picked = ref[np.arange(len(best)), idx[rows]]
        e_arg = max(e_arg, float(((picked - best) / s).max()))
    errs["pairwise_argmin.min"] = e_min
    errs["pairwise_argmin.argmin_gap"] = e_arg

    # d2_update: one center's D^2 sweep (differences, no cancellation).
    w = rng.uniform(0.0, 2.0 * np.median(d2), size=n)
    out = np.asarray(jax.block_until_ready(
        ops.d2_update(x, x[ci[0]], jnp.asarray(w, jnp.float32))), np.float64)
    wf = np.asarray(np.float32(w), np.float64)
    ref = np.minimum(wf, ((xf - xf[ci[0]]) ** 2).sum(1))
    errs["d2_update"] = float((np.abs(out - ref)
                               / np.maximum(ref, 1e-30)).max())

    # tree_sep_update: codes agreeing with the center's on a prefix of
    # heights, so every separation level occurs.
    c_lo = rng.integers(-2 ** 31, 2 ** 31, size=h, dtype=np.int64)
    c_hi = rng.integers(-2 ** 31, 2 ** 31, size=h, dtype=np.int64)
    agree = rng.integers(0, h + 1, size=n)
    same = np.arange(h)[:, None] < agree[None, :]
    lo = np.where(same, c_lo[:, None], c_lo[:, None] ^ 1).astype(np.int32)
    hi = np.where(same, c_hi[:, None], c_hi[:, None] + 1).astype(np.int32)
    tscale, levels = 2.0 * np.sqrt(d) * 500.0, h + 1
    w = rng.uniform(0.0, tscale ** 2, size=n).astype(np.float32)
    out = np.asarray(jax.block_until_ready(ops.tree_sep_update(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(c_lo.astype(np.int32)),
        jnp.asarray(c_hi.astype(np.int32)), jnp.asarray(w),
        scale=float(tscale), num_levels=levels, block_n=512)), np.float64)
    dist = tscale * (2.0 ** (-agree.astype(np.float64)) - 2.0 ** (1 - levels))
    ref = np.minimum(w.astype(np.float64), dist * dist)
    errs["tree_sep_update"] = float(
        (np.abs(out - ref) / np.maximum(ref, tscale ** 2 * 1e-12)).max())

    # lsh_bucket_accept: b candidates against k centers, of which the
    # first `live` are open; keys collide now and then.
    live, c2 = k - 12, 4.0
    qi = rng.choice(n, b, replace=False)
    qk = rng.integers(0, 64, size=(2, l, b)).astype(np.int32)
    ck = rng.integers(0, 64, size=(2, l, k)).astype(np.int32)
    mtd2 = rng.uniform(0.0, 2.0 * np.median(d2), size=b).astype(np.float32)
    mtd2[::7] = 0.0
    d2_min, p = jax.block_until_ready(ops.lsh_bucket_accept(
        *(jnp.asarray(a) for a in (qk[0], qk[1])), x[qi],
        *(jnp.asarray(a) for a in (ck[0], ck[1])), x[ci],
        jnp.asarray(mtd2), live, c2=c2))
    d2_min, p = np.asarray(d2_min, np.float64), np.asarray(p, np.float64)
    collide = ((qk[0][:, :, None] == ck[0][:, None, :])
               & (qk[1][:, :, None] == ck[1][:, None, :])).any(0)
    collide[:, live:] = False
    full = next(_d2_f64(xf[qi], cf))[1]
    hit = collide.any(1)
    ref = np.where(collide, full, np.inf).min(1)
    s = scale[qi]
    miss = d2_min >= LSH_MISS_FLOOR
    # A bucket miss that is not one (or the reverse) is an error of 1.
    e = 1.0 if not np.array_equal(~miss, hit) else 0.0
    e = max(e, float((np.abs(d2_min[hit] - ref[hit]) / s[hit]).max()))
    m = mtd2.astype(np.float64)
    ok = m > 0
    p_ref = np.where(ok, ref / np.maximum(c2 * m, 1e-30), 0.0)
    sel = ok & hit
    # p's error in units of what an expansion error of 1 relative to
    # |x|^2 + |c|^2 moves it by, plus 1 relative of its own value.
    ep = float((np.abs(p[sel] - p_ref[sel])
                / (s[sel] / (c2 * m[sel]) + p_ref[sel])).max())
    if not (bool((p[~ok] == 0.0).all()) and bool((p[ok & ~hit] > 1.0).all())):
        ep = max(ep, 1.0)    # covered points accept, or misses do not
    errs["lsh_bucket_accept.d2"] = e
    errs["lsh_bucket_accept.p"] = ep
    return errs
