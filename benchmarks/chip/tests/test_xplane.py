"""The trace reduction, on a hand-made trace and on a recorded one.

`data/reseed_k8.xplane.pb.gz` is a profiler trace recorded on a TPU v5 lite
around two `refit` calls of `rejection/device` (4,096 x 16, k = 8), with
the benchmark's window and call spans.
"""

from __future__ import annotations

import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import _tiny  # noqa: F401  (puts the benchmark on sys.path)

import roofline
import xplane

RECORDED = Path(__file__).resolve().parent / "data" / "reseed_k8.xplane.pb.gz"
TREE = ("%tree_sep_update_pallas.33 = f32[1,4096]{1,0:T(1,128)} custom-call("
        "s32[16,4096]{1,0:T(8,128)} %a, s32[16,4096]{1,0} %b, s32[16,1]{1,0} "
        "%c, s32[16,1]{1,0} %d, f32[1,4096]{1,0} %e), custom_call_target=x, "
        "operand_layout_constraints={s32[16,4096]{1,0}, s32[16,4096]{1,0}, "
        "s32[16,1]{1,0}, s32[16,1]{1,0}, f32[1,4096]{1,0}}")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 1000), _ev("bench.refit", 0, 100),
        _ev("bench.block_until_ready", 100, 800)])])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("%while.1 = (s32[]) while(s32[] %x)", 150, 500),
        _ev(TREE, 200, 100), _ev(TREE, 400, 100),
        _ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %y)", 900, 50)])])
    return NS(planes=[host, dev])


def test_busy_self_time_and_gaps():
    s = xplane.reduce_profile(_profile(), chips=1)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(550e-9)
    tree = s.kernel("tree_sep_update_pallas")
    assert tree.calls == 2 and tree.seconds == pytest.approx(200e-9)
    assert s.ops["while"].seconds == pytest.approx(300e-9)
    # Gaps, named at their middles: [0, 150) in bench.refit, [650, 900)
    # in bench.block_until_ready, [950, 1000) after every span closed.
    labels = dict(s.breakdown()["idle_gaps"])
    assert labels == pytest.approx({"bench.refit": 150e-9,
                                    "bench.block_until_ready": 250e-9,
                                    "no benchmark span": 50e-9})
    assert s.idle_share == pytest.approx(0.45)


def test_parse_call_shapes_and_bytes():
    call = xplane.parse_call(TREE)
    assert call["results"] == [("f32", (1, 4096))]
    assert [d for _, d in call["operands"]] == [
        (16, 4096), (16, 4096), (16, 1), (16, 1), (1, 4096)]
    assert xplane.nbytes(call["operands"]) == 4 * (2 * 16 * 4096 + 32 + 4096)


def test_roofline_share_of_the_hand_made_trace():
    from registry import Registry

    run = NS(registry=Registry(), device_kind="TPU v5 lite",
             trace=xplane.reduce_profile(_profile(), chips=1))
    nbytes = 4 * (2 * 16 * 4096 + 32 + 2 * 4096)
    least = 2 * nbytes / roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert roofline.share(run, "tree_sep_update") == pytest.approx(
        100 * least / 200e-9)
    assert roofline.share(run, "lsh_bucket_accept") is None
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def _one_call_per_chip(chips: int, seconds: float):
    """A trace in which each of `chips` chips runs one TREE call."""
    ns = seconds * 1e9
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 2 * ns)])])
    devices = [NS(name=f"/device:TPU:{i}", lines=[NS(
        name="XLA Ops", events=[_ev(TREE, 100 + 7 * i, ns)])])
        for i in range(chips)]
    return NS(planes=[host, *devices])


def test_roofline_share_is_per_chip():
    from registry import Registry

    shares = []
    for chips in (1, 2, 4):
        trace = xplane.reduce_profile(_one_call_per_chip(chips, 1e-6),
                                      chips=chips)
        assert trace.chips == chips
        assert trace.kernel("tree_sep_update_pallas").calls == chips
        run = NS(registry=Registry(), device_kind="TPU v5 lite", trace=trace)
        shares.append(roofline.share(run, "tree_sep_update"))
    nbytes = 4 * (2 * 16 * 4096 + 32 + 2 * 4096)
    one = 100 * nbytes / roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] / 1e-6
    assert shares == pytest.approx([one] * 3)


LSH = ("%lsh_bucket_accept_pallas.7 = (f32[1,512]{1,0}, f32[1,512]{1,0}) "
       "custom-call(s32[16,512]{1,0} %a, s32[16,512]{1,0} %b, "
       "f32[512,74]{1,0} %q, s32[16,512]{1,0} %c, s32[16,512]{1,0} %d, "
       "f32[512,74]{1,0} %e, f32[1,512]{1,0} %p, f32[1,512]{1,0} %w), "
       "custom_call_target=x")


def test_lsh_roofline_counts_six_passes_of_its_product():
    from registry import Registry

    prof = _profile()
    prof.planes[1].lines[0].events.append(_ev(LSH, 960, 40))
    run = NS(registry=Registry(), device_kind="TPU v5 lite",
             trace=xplane.reduce_profile(prof, chips=1))
    peak = roofline.peaks("TPU v5 lite")
    flops = 6 * 2.0 * 512 * 512 * 74
    nbytes = 4 * (4 * 16 * 512 + 2 * 512 * 74 + 2 * 512 + 2 * 512)
    assert flops / nbytes > peak["flops_per_s"] / peak["hbm_bytes_per_s"]
    assert roofline.share(run, "lsh_bucket_accept") == pytest.approx(
        100 * flops / peak["flops_per_s"] / 40e-9)


def test_window_span_is_required():
    prof = _profile()
    prof.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        xplane.reduce_profile(prof, chips=1)


def test_recorded_trace():
    from jax.profiler import ProfileData

    from registry import Registry

    profile = ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes()))
    s = xplane.reduce_profile(profile, chips=1)
    assert 0.0 < s.busy_s <= s.window_s
    tree = s.kernel("tree_sep_update_pallas")
    lsh = s.kernel("lsh_bucket_accept_pallas")
    assert tree.calls == 2 * 8 * 3          # 2 seedings, k = 8, 3 trees
    assert lsh.calls > 0
    run = NS(registry=Registry(), device_kind="TPU v5 lite", trace=s)
    for kernel in ("tree_sep_update", "lsh_bucket_accept"):
        assert 0.0 < roofline.share(run, kernel) <= 100.0
    bd = s.breakdown()
    assert len(bd["device_ops"]) <= 10 and bd["idle_gaps"]
