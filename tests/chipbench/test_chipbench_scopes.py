"""The readers of the program's layer scopes and set-up counters, on
synthetic events, on the program's own registry, and on a small scoped
trace kept beside this file: one window of one solve of the 7-point cell
at an 8^3 grid, recorded on a TPU v5e and trimmed to the device's op line
and the host spans (``data/scoped7_8.json`` says how, and maps its
instructions to the JAX ops they came from)."""

import dataclasses
import json
import os
import sys
import types

import pytest

from chipbench_testutil import HERE

from repro.obs.metrics import MetricsRegistry

from chipbench import catalog, counters, harness, peaks, scopes, trace
from chipbench.entries import Answer
from chipbench.metrics import (
    backend_compile_s,
    collective_ms_per_iter,
    halo_scope_ms_per_iter,
    partition_csr_s,
    reductions_scope_ms_per_iter,
    spmv_ms_per_iter,
    spmv_roofline,
    spmv_scope_ms_per_iter,
    spmv_scope_roofline,
    unattributed_share,
    unscoped_share,
    vecops_ms_per_iter,
)

SCOPE_READERS = (spmv_scope_ms_per_iter, spmv_scope_roofline,
                 reductions_scope_ms_per_iter, halo_scope_ms_per_iter,
                 unscoped_share)
BODY = "jit(solve)/shard_map/while/body/"


def _ev(dev, dur, op, name="fusion.1"):
    return trace.Event(device=dev, name=name, start_ns=0.0, dur_ns=dur, op=op)


def _ctx(events, iters=10, chips=2):
    """A context over ``events`` on ``chips`` devices of a 4^3 grid each."""
    cell = catalog.cell("poisson7_weak.ring4")
    problem = cell.problem.build(dict(cell.config, local_grid=[4, 4, 4]), chips)
    busy = {d: sum(ev.dur_ns for ev in events if ev.device == d)
            for d in range(chips)}
    tr = trace.Trace(events=events, busy_ns=busy, window_ns=1e6,
                     window_start_ns=0.0, host_spans=[])
    return harness.Context(
        problem=problem, config=cell.config, spans={}, window_s=1e-3,
        answers=[Answer(x=None, iters=iters, relres=0.0, transfer_s=0.0)],
        trace=tr, peaks=peaks.for_kind("TPU v5 lite"))


# device 0: interior matvec 50, halo nested in the overlapped SpMV 10 (of
# it the ppermute 4), reductions 30 (a psum 2 of it), loop counter 5, a
# compiler copy 5; device 1 the same with a longer halo
SYNTH = [
    _ev(0, 50, BODY + "spmv/overlap/interior/gather"),
    _ev(0, 6, BODY + "spmv/overlap/halo/gather"),
    _ev(0, 4, BODY + "spmv/overlap/halo/ppermute", "collective-permute-done"),
    _ev(0, 28, BODY + "reductions/dot_general"),
    _ev(0, 2, BODY + "reductions/psum", "all-reduce.1"),
    _ev(0, 5, BODY + "add"),
    _ev(0, 5, "", "copy.3"),
    _ev(1, 50, BODY + "spmv/overlap/interior/gather"),
    _ev(1, 20, BODY + "spmv/overlap/halo/ppermute", "collective-permute-done"),
    _ev(1, 30, BODY + "reductions/mul;" + BODY + "reductions/add"),
]


def test_scope_steps_of_nested_and_fused_ops():
    nested, fused, bare = SYNTH[2], SYNTH[-1], SYNTH[6]
    assert {"spmv", "overlap", "halo", "ppermute"} <= scopes.steps(nested)
    assert scopes.in_scope("halo")(nested) and scopes.in_scope("spmv")(nested)
    assert not scopes.in_scope("halo")(SYNTH[0])
    assert scopes.in_scope("reductions")(fused) and not scopes.unscoped(fused)
    assert scopes.steps(bare) == set() and scopes.unscoped(bare)
    assert scopes.unscoped(SYNTH[5])  # the loop counter


@pytest.mark.parametrize("reader,want", [
    (spmv_scope_ms_per_iter, 70 / 1e6 / 10),
    (reductions_scope_ms_per_iter, 30 / 1e6 / 10),
    (halo_scope_ms_per_iter, 20 / 1e6 / 10),
    (unscoped_share, 100 * 10 / 100),
], ids=lambda v: getattr(v, "__name__", ""))
def test_scope_reader_on_synthetic_events(reader, want):
    assert reader.read(_ctx(SYNTH)) == pytest.approx(want)


def test_scope_roofline_is_csr_bytes_over_scope_time():
    ctx = _ctx(SYNTH)
    nbytes = spmv_roofline.chip_bytes(ctx.problem)[1]  # device 1: most SpMV
    want = 100 * ctx.spmv_calls * nbytes / 819e9 / (70 / 1e9)
    assert spmv_scope_roofline.read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("reader", SCOPE_READERS, ids=lambda m: m.__name__)
def test_scope_reader_finds_nothing_returns_none(reader):
    ctx = _ctx(SYNTH)
    assert reader.read(dataclasses.replace(ctx, trace=None)) is None
    no_ops = dataclasses.replace(ctx.trace, events=[])
    assert reader.read(dataclasses.replace(ctx, trace=no_ops)) is None
    # a program without the scopes: the same ops, named as before them
    bare = [ev._replace(op=ev.op.replace("spmv/", "").replace("overlap/", "")
                        .replace("halo/", "").replace("reductions/", ""))
            for ev in SYNTH]
    assert reader.read(_ctx(bare)) is None


def _registry(monkeypatch, **values):
    """Stand in a program registry holding ``values``."""
    reg = MetricsRegistry()
    for name, v in values.items():
        reg.counter(name).inc(v)
    mod = types.SimpleNamespace(REGISTRY=reg)
    monkeypatch.setitem(sys.modules, "repro.obs.metrics", mod)


@pytest.mark.parametrize("reader,counter", [
    (partition_csr_s, "setup_partition_csr_seconds"),
    (backend_compile_s, "warm_backend_compile_seconds"),
], ids=["partition_csr_s", "backend_compile_s"])
def test_counter_readers(monkeypatch, reader, counter):
    ctx = _ctx(SYNTH)
    _registry(monkeypatch, **{counter: 2.5})
    assert reader.read(ctx) == 2.5
    _registry(monkeypatch)  # the counter was never made
    assert reader.read(ctx) is None
    # a program older than the registry
    monkeypatch.setitem(sys.modules, "repro.obs.metrics", types.ModuleType("m"))
    assert counters.value(counter) is None and reader.read(ctx) is None


DATA = os.path.join(HERE, "data")


@pytest.fixture(scope="module")
def scoped():
    with open(os.path.join(DATA, "scoped7_8.json")) as f:
        meta = json.load(f)
    tr = trace.reduce(os.path.join(DATA, "scoped7_8.xplane.pb"),
                      meta["op_names"])
    cell = catalog.cell(meta["workload"])
    config = dict(cell.config, local_grid=meta["local_grid"])
    answers = [Answer(x=None, iters=i, relres=0.0, transfer_s=0.0)
               for i in meta["iters"]]
    return harness.Context(
        problem=cell.problem.build(config, cell.chips), config=config,
        answers=answers, spans={}, window_s=tr.window_s, trace=tr,
        peaks=peaks.for_kind(meta["device_kind"]))


def test_scoped_trace_names_every_layer(scoped):
    tr = scoped.trace
    assert list(tr.busy_ns) == [0] and tr.events
    ops = tr.time_ns(lambda ev: True)[0]
    spmv = tr.time_ns(spmv_scope_ms_per_iter.is_spmv)[0]
    red = tr.time_ns(reductions_scope_ms_per_iter.is_reduction)[0]
    bare = tr.time_ns(scopes.unscoped)[0]
    # one chip: the SpMV and the reductions are disjoint and, with the
    # unscoped ops, make up the device's op time
    assert spmv > 0 and red > 0
    assert spmv + red + bare == pytest.approx(ops)
    assert tr.time_ns(halo_scope_ms_per_iter.is_halo)[0] == 0


def test_scoped_trace_readers_agree_with_the_op_rules(scoped):
    """The scope readers against PR 12's op-name rules on the same trace:
    the SpMV scope holds the gathers and einsums and a little more; the
    reductions and the unscoped ops are the rest, as the op rules' vector
    ops and collectives are."""
    spmv_rule = spmv_ms_per_iter.read(scoped)
    spmv = spmv_scope_ms_per_iter.read(scoped)
    assert spmv_rule <= spmv <= 1.2 * spmv_rule
    rest = (vecops_ms_per_iter.read(scoped) + spmv_rule
            + collective_ms_per_iter.read(scoped))
    ops_ms = sum(ev.dur_ns for ev in scoped.trace.events) / 1e6 / scoped.iterations
    assert rest == pytest.approx(ops_ms)
    red = reductions_scope_ms_per_iter.read(scoped)
    bare = unscoped_share.read(scoped) / 100 * ops_ms
    assert spmv + red + bare == pytest.approx(ops_ms)
    assert unscoped_share.read(scoped) >= unattributed_share.read(scoped)
    assert 0 < spmv_scope_roofline.read(scoped) <= spmv_roofline.read(scoped)
