"""The trace reducer and the per-layer readers, on a small trace kept
beside this file: one window of one solve of the 7-point cell at an 8^3
grid, recorded on a TPU v5e and trimmed to the device's op line and the
benchmark's host spans (``data/poisson7_8.json`` says how, and maps its
instructions to the JAX ops they came from)."""

import dataclasses
import json
import os

import pytest

from chipbench_testutil import HERE

from chipbench import catalog, harness, peaks, trace
from chipbench.entries import Answer
from chipbench.metrics import (
    collective_ms_per_iter,
    idle_share,
    jax_start_s,
    spmv_ms_per_iter,
    spmv_roofline,
    unattributed_share,
    vecops_ms_per_iter,
)

DATA = os.path.join(HERE, "data")
META = json.load(open(os.path.join(DATA, "poisson7_8.json")))


@pytest.fixture(scope="module")
def tr():
    return trace.reduce(os.path.join(DATA, "poisson7_8.xplane.pb"),
                        META["op_names"])


@pytest.fixture(scope="module")
def ctx(tr):
    cell = catalog.cell(META["workload"])
    config = dict(cell.config, local_grid=META["local_grid"])
    problem = cell.problem.build(config, cell.chips)
    answers = [Answer(x=None, iters=i, relres=0.0, transfer_s=1e-3) for i in META["iters"]]
    return harness.Context(problem=problem, config=config, answers=answers,
                           spans={"partition_s": 0.5, "compile_s": 2.0,
                                  "jax_start_s": 9.0},
                           window_s=tr.window_s, trace=tr,
                           peaks=peaks.for_kind(META["device_kind"]))


def test_reducer_reads_one_device_window(tr):
    assert list(tr.busy_ns) == [0]
    assert tr.events and all(ev.dur_ns > 0 for ev in tr.events)
    assert 0 < tr.busy_ns[0] <= tr.window_ns
    lo, hi = tr.window_start_ns, tr.window_start_ns + tr.window_ns
    assert all(lo <= ev.start_ns and ev.start_ns + ev.dur_ns <= hi
               for ev in tr.events)
    assert {"chipbench.window", "chipbench.solve"} <= {s[0] for s in tr.host_spans}


def test_layers_partition_the_device_ops(tr):
    spmv = tr.time_ns(spmv_ms_per_iter.is_spmv)[0]
    vec = tr.time_ns(vecops_ms_per_iter.is_vecop)[0]
    coll = tr.time_ns(collective_ms_per_iter.is_collective)[0]
    # one chip: the psums run over one device and take next to nothing
    assert spmv > 0 and vec > 0 and 0 <= coll < 1e-3 * (spmv + vec)
    assert spmv + vec + coll == pytest.approx(sum(ev.dur_ns for ev in tr.events))


@pytest.mark.parametrize("reader,lo,hi", [
    (spmv_ms_per_iter, 0.0, 1e3),
    (vecops_ms_per_iter, 0.0, 1e3),
    (spmv_roofline, 0.0, 100.0),
    (idle_share, 0.0, 100.0),
    (unattributed_share, 0.0, 100.0),
])
def test_reader_in_range(ctx, reader, lo, hi):
    v = reader.read(ctx)
    assert v is not None and lo < v < hi, v


READERS = (spmv_ms_per_iter, spmv_roofline, vecops_ms_per_iter,
           collective_ms_per_iter, idle_share, unattributed_share)


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__)
def test_reader_finds_nothing_returns_none(ctx, tr, reader):
    untraced = harness.Context(**{**ctx.__dict__, "trace": None})
    assert reader.read(untraced) is None
    if reader is not idle_share:  # reads the window itself
        no_ops = dataclasses.replace(tr, events=[])
        assert reader.read(harness.Context(**{**ctx.__dict__, "trace": no_ops})) is None


def test_vecops_silent_without_spmv(ctx, tr):
    """Where the SpMV's rule finds no SpMV, its time would land among the
    vector ops unseen: both readers stay silent."""
    no_spmv = dataclasses.replace(tr, events=[
        ev for ev in tr.events if not spmv_ms_per_iter.is_spmv(ev)])
    c = harness.Context(**{**ctx.__dict__, "trace": no_spmv})
    assert spmv_ms_per_iter.read(c) is None and vecops_ms_per_iter.read(c) is None
    assert vecops_ms_per_iter.read(ctx) > 0


def test_host_span_readers(ctx):
    assert jax_start_s.read(ctx) == 9.0
    assert jax_start_s.read(harness.Context(**{**ctx.__dict__, "spans": {}})) is None


def test_unattributed_share_and_check():
    evs = [_ev(0, 0, 97, op="jit(solve)/while/body/gather"), _ev(0, 97, 3),
           _ev(1, 0, 99, op="jit(solve)/add"), _ev(1, 99, 1)]
    tr = trace.Trace(events=evs, busy_ns={0: 100.0, 1: 100.0}, window_ns=100.0,
                     window_start_ns=0.0, host_spans=[])
    assert tr.unattributed_share() == pytest.approx(0.03)
    with pytest.raises(trace.UnattributedError):
        trace.check_attributed(tr)
    ok = dataclasses.replace(tr, events=evs[:1] + evs[2:])
    assert ok.unattributed_share() == pytest.approx(0.01)
    trace.check_attributed(ok)


def test_spmv_roofline_is_bytes_over_bandwidth_over_time(ctx, tr):
    spmv_s = tr.time_ns(spmv_ms_per_iter.is_spmv)[0] / 1e9
    nbytes = spmv_roofline.chip_bytes(ctx.problem)[0]
    want = 100 * ctx.spmv_calls * nbytes / 819e9 / spmv_s
    assert spmv_roofline.read(ctx) == pytest.approx(want)


def test_breakdown_shape(tr):
    bd = tr.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    for key in bd:
        assert 0 < len(bd[key]) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in bd[key])


def _ev(dev, start, dur, name="fusion.1", op=""):
    return trace.Event(device=dev, name=name, start_ns=start, dur_ns=dur, op=op)


def test_op_names_and_layer_rules():
    hlo = ('  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'calls=%fc, metadata={op_name="jit(solve)/while/body/gather" '
           'stack_frame_id=3}\n'
           '  ROOT %all-reduce.2 = f32[] all-reduce(f32[] %x), '
           'metadata={op_name="jit(solve)/while/body/psum"}\n')
    ops = trace.op_names(hlo)
    assert ops == {"fusion.7": "jit(solve)/while/body/gather",
                   "all-reduce.2": "jit(solve)/while/body/psum"}
    spmv = [_ev(0, 0, 1, op=o) for o in (
        "jit(solve)/while/body/gather", "jit(solve)/rk,rk->r/dot_general",
        "jit(solve)/while/body/bk,bk->b/dot_general",
        "jit(solve)/while/body/scatter-add")]
    other = [_ev(0, 0, 1, op=o) for o in (
        "jit(solve)/while/body/dot_general", "", "jit(solve)/while/body/add")]
    assert all(spmv_ms_per_iter.is_spmv(e) for e in spmv)
    assert not any(spmv_ms_per_iter.is_spmv(e) for e in other)
    coll = [_ev(0, 0, 1, name="all-reduce.2"),
            _ev(0, 0, 1, name="collective-permute-start.1"),
            _ev(0, 0, 1, name="fusion.3", op="jit(solve)/while/body/ppermute")]
    assert all(collective_ms_per_iter.is_collective(e) for e in coll)
    assert not any(collective_ms_per_iter.is_collective(e) for e in spmv + other)


def test_busy_union_and_idle_gaps_on_synthetic_events():
    evs = [_ev(0, 10, 10), _ev(0, 15, 10), _ev(0, 40, 5), _ev(1, 0, 100)]
    tr = trace.Trace(events=evs, busy_ns={0: 20.0, 1: 100.0}, window_ns=100.0,
                     window_start_ns=0.0,
                     host_spans=[("chipbench.window", 0, 100),
                                 ("chipbench.put", 25, 40)])
    assert trace._union([(10, 20), (15, 25), (40, 45)]) == [[10, 25], [40, 45]]
    assert tr.idle_gaps(0) == [(0.0, 10), (25, 40), (45, 100.0)]
    assert tr.host_at(25, 40) == "chipbench.put"
    assert tr.host_at(50, 60) == "host (no span)"
    assert tr.time_ns(lambda ev: ev.device == 0) == {0: 25.0, 1: 0.0}


RING = json.load(open(os.path.join(DATA, "ring4_4.json")))


@pytest.fixture(scope="module")
def ring():
    """A quarter of a ring window on four TPU v5e chips (ring4_4.json):
    the 7-point problem extruded over four z-slabs, as the deferred ring
    cell runs it."""
    return trace.reduce(os.path.join(DATA, "ring4_4.xplane.pb"),
                        RING["op_names"])


def test_ring_trace_has_four_devices_and_collectives(ring):
    assert sorted(ring.busy_ns) == [0, 1, 2, 3]
    coll = ring.time_ns(collective_ms_per_iter.is_collective)
    spmv = ring.time_ns(spmv_ms_per_iter.is_spmv)
    assert all(coll[d] > 0 and spmv[d] > coll[d] for d in range(4))
    assert all(0 < ring.busy_ns[d] <= ring.window_ns for d in range(4))


def test_ring_readers_take_the_busiest_device(ctx, ring):
    cell = catalog.cell(META["workload"])
    problem = cell.problem.build(dict(cell.config, local_grid=RING["local_grid"]), 4)
    answers = [Answer(x=None, iters=10, relres=0.0, transfer_s=1e-3)]
    rctx = harness.Context(**{**ctx.__dict__, "trace": ring, "problem": problem,
                              "answers": answers})
    coll = ring.time_ns(collective_ms_per_iter.is_collective)
    assert collective_ms_per_iter.read(rctx) == pytest.approx(
        max(coll.values()) / 1e6 / 10)
    assert idle_share.read(rctx) == pytest.approx(
        100 * (1 - max(ring.busy_ns.values()) / ring.window_ns))
    assert 0 < spmv_roofline.read(rctx) < 100
