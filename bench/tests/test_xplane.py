"""The trace reducer on hand-built planes, where every number is known."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import xplane


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(xplane.WINDOW, 1000, 10000),
        ev(xplane.BATCH, 2000, 3000),
        ev(xplane.BATCH, 7000, 2000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit__sa_chains(1)", 2500, 2000),
                                       ev("jit_other(2)", 8000, 1000),
                                       ev("jit_before(3)", 0, 900)]),
        NS(name="XLA Ops", events=[ev("fusion.1", 2500, 1200),
                                   ev("delta_cost", 3400, 1100),
                                   ev("fusion.1", 8000, 1000),
                                   ev("fusion.1", 10500, 1000)])])
    return [host, dev, NS(name="/device:TPU:0 SparseCore 0", lines=[])]


def test_busy_union_and_window():
    r = xplane.reduce_planes(planes())
    assert r["window_s"] == pytest.approx(10000e-9)
    # ops: [2500,3700] U [3400,4500] = 2000; [8000,9000] = 1000;
    # [10500,11500] clipped to the window end 11000 = 500
    assert r["busy_s"] == pytest.approx(3500e-9)
    assert r["chips"] == 1


def test_program_and_op_times():
    r = xplane.reduce_planes(planes())
    assert r["modules_s"] == pytest.approx({"jit__sa_chains(1)": 2000e-9,
                                            "jit_other(2)": 1000e-9})
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 2700e-9, "delta_cost": 1100e-9})


def test_gaps_labelled_by_host_annotation():
    r = xplane.reduce_planes(planes())
    gaps = sorted((label, round(s * 1e9)) for label, s in r["idle_gaps"])
    # window 1000..11000; busy 2500..4500, 8000..9000, 10500..11000;
    # batches 2000..5000 and 7000..9000
    assert gaps == [("host in service batch", 500),
                    ("host in service batch", 500),
                    ("host in service batch", 1000),
                    ("host outside any batch", 1000),
                    ("host outside any batch", 1500),
                    ("host outside any batch", 2000)]
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"])


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_planes(planes()[:1])


SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_recorded_tpu_trace():
    """The trace ``data/record_small_trace.py`` recorded on one TPU v5e: one
    20-step device-SA search inside the window and batch annotations."""
    r = xplane.reduce_file(str(SMALL))
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(15587113e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    sa = [v for k, v in r["modules_whole"].items() if "_sa_chains" in k]
    assert [n for n, _ in sa] == [1]
    assert sa[0][1] == pytest.approx(r["modules_s"][next(
        k for k in r["modules_s"] if "_sa_chains" in k)])
    # the SA program's scan is one while op that spans the busy time
    assert sa[0][1] <= r["busy_s"]
    assert all(" = " not in name for name, _ in r["device_ops"])
    # the ten longest gaps, so at most the idle time
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] <= \
        r["window_s"] * (1 + 1e-9)
    assert {label for label, _ in r["idle_gaps"]} <= {
        "host in service batch", "host outside any batch"}


def test_profiler_planes_are_read_once():
    """The profiler's plane sequence yields nothing on a second walk; the
    reducer must read it once."""
    import jax
    planes = jax.profiler.ProfileData.from_file(str(SMALL)).planes
    assert xplane.reduce_planes(planes)["chips"] == 1


def test_whole_programs_exclude_those_cut_by_the_window():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(xplane.WINDOW, 1000, 10000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit__sa_chains(1)", 500, 1000),
                                       ev("jit__sa_chains(1)", 2000, 3000),
                                       ev("jit__sa_chains(1)", 6000, 3000),
                                       ev("jit__sa_chains(1)", 10000, 3000)]),
        NS(name="XLA Ops", events=[ev("while", 2000, 3000)])])
    r = xplane.reduce_planes([host, dev])
    assert r["modules_whole"] == {"jit__sa_chains(1)": (2, 6000e-9)}
    assert r["modules_s"] == {"jit__sa_chains(1)": pytest.approx(7500e-9)}
