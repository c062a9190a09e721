"""The reduction of the program's own spans, launches and scopes
(phases.py): on hand-made intervals and HLO text, and on traces recorded
on a TPU v5e (testdata/)."""
import gzip
import json
import pathlib
import shutil

import numpy as np
import pytest

from benchmarks.chip import harness, phases, trace
from benchmarks.chip import profile_cell, run, tiny
from benchmarks.chip.profile_cell import PHASE_METRICS, SAMPLE

DATA = pathlib.Path(__file__).parent / "testdata"
# three flushes of 8 reads and three 32-point ingests, bench/* spans only
# (recorded before the program had spans of its own)
OLD = DATA / "v5e_flushes.xplane.pb.gz"
# profile_cell.py --sample on one v5e at capacity 2^20: flushes of 8, 4
# and 2 fresh reads, with the program's coal/* spans and probe/* scopes,
# the compiled text of their probe programs and the coalescer's counters
NEW = DATA / f"{SAMPLE}.xplane.pb.gz"
OLD_READERS = ("engine.flush_ms", "engine.host_pct", "cache.hit_pct",
               "prober.device_ms", "prober.visits", "device.idle_pct")


def _unzip(src: pathlib.Path, tmp: pathlib.Path) -> pathlib.Path:
    path = tmp / src.name.removesuffix(".gz")
    with gzip.open(src) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return path


def _run(summary, **kw) -> harness.Run:
    return harness.Run(seconds=1.0, setup_s=2.0, due=np.zeros(0),
                       done=np.zeros(0), est=np.zeros(0), prov=[],
                       nvisited=kw.pop("nvisited", []), trace=summary, **kw)


def _flush_summary() -> phases.PhaseSummary:
    """One flush inside a window: lookup (holding a sync), probe, merge."""
    spans = [("bench/window", 0, 1000, 0), ("bench/flush", 100, 900, 1),
             ("coal/flush", 110, 890, 2), ("coal/lookup", 120, 400, 3),
             ("coal/sync", 300, 400, 4), ("coal/probe", 400, 500, 3),
             ("coal/merge", 600, 880, 3)]
    parents = [None, 0, 1, 2, 3, 2, 2]
    busy = np.array([[150, 250], [420, 600]], np.float64)
    calls = [(150, 250, "jit_a(1)"), (160, 200, "jit_b(2)"),
             (420, 600, "jit_a(1)"), (950, 960, "jit_c(3)")]
    return phases.PhaseSummary((0, 1000), [busy], {}, {}, spans, parents,
                               calls, [])


def test_gaps_and_idle_named_by_innermost_span():
    s = _flush_summary()
    # idle in the window: [0,150) in no span, [250,420) in coal/sync,
    # [600,1000) in coal/merge until 880
    assert [g[0] for g in s.idle_gaps()] == ["coal/merge", "coal/sync",
                                             "none"]
    idle = s.idle_by_phase("coal/flush")
    # flush 780 ns, 280 busy: 500 idle; lookup 180 of which sync 100;
    # probe 20; merge 280; the flush itself outside every child 20
    assert {k: round(v * 1e9) for k, v in idle.items()} == {
        "coal/flush": 20, "coal/lookup": 80, "coal/sync": 100,
        "coal/probe": 20, "coal/merge": 280}
    # host share of bench/flush is unchanged by the coal/* spans
    assert s.host_share("bench/flush") == pytest.approx(1 - 280 / 800)


def test_launch_counts_per_span_and_phase():
    s = _flush_summary()
    assert s.launches_in("coal/flush") == [3]
    assert s.launches_in("coal/sync") == [0]
    assert s.launches_by_phase("coal/flush") == {"coal/lookup": 2.0,
                                                 "coal/probe": 1.0}
    run = _run(s)
    assert harness.load_metric("engine.programs_per_flush").read(run) == 3.0
    # a trace with no device plane has no launches to count
    bare = trace.TraceSummary((0, 1), [], {}, {}, [])
    assert harness.load_metric("engine.programs_per_flush").read(
        _run(bare)) is None


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %add.1 = f32[8]{0} add(f32[8]{0} %param_0, f32[8]{0} %param_0), \
metadata={op_name="jit(step)/vmap(probe/rings)/add"}
  ROOT %mul.1 = f32[8]{0} multiply(%add.1, %param_0), \
metadata={op_name="reduce_window_sum"}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0:T(256)} fusion(%a), kind=kLoop, \
calls=%fused_computation.1, metadata={op_name="reduce_window_sum"}
  %reduce-window.3 = f32[8]{0} reduce-window(%fusion.1), window={size=8}
  %tuple.4 = (s32[], f32[8]{0}) tuple(%a, %reduce-window.3)
  %while.2 = (s32[], f32[8]{0:T(256)}) while(%tuple.4), condition=%c, \
body=%b, metadata={op_name="jit(step)/probe/slab/while"}
  ROOT %copy.5 = f32[8]{0} copy(%a)
}
"""


class _Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end


def test_scope_attribution():
    h = phases.HloScopes(HLO)
    assert h.module == "jit_step"
    scope = {n: h.scope(n) for n in ("%fusion.1", "%reduce-window.3",
                                     "%while.2", "%copy.5", "%a")}
    # a fusion takes its members' scope; an op the lowering left bare
    # takes its operand's; a parameter copy stays unscoped
    assert scope == {"%fusion.1": "rings", "%reduce-window.3": "rings",
                     "%while.2": "slab", "%copy.5": None, "%a": None}
    calls = [(0, 100, "jit_step(77)")]
    events = [
        _Ev("%fusion.1 = f32[8]{0:T(256)} fusion(f32[8]{0} %a), "
            "kind=kLoop, calls=%fused_computation.1", 0, 10),
        _Ev("%while.2 = (s32[], f32[8]{0:T(256)}) while((s32[], f32[8]{0})"
            " %tuple.4), condition=%c, body=%b", 20, 80),
        # a body op the text does not hold runs inside the while
        _Ev("%add.7 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)", 30, 40),
        _Ev("%copy.5 = f32[8]{0} copy(f32[8]{0} %a)", 85, 90)]
    ops = phases._scoped_ops(calls, events, [h])
    assert ops == [(0, "fusion.1", 10, "rings"), (0, "while.2", 50, "slab"),
                   (0, "add.7", 10, "slab"), (0, "copy.5", 5, None)]
    s = phases.PhaseSummary((0, 1000), [], {}, {}, [], [], calls, ops)
    assert s.scope_ms("jit_step") == pytest.approx(
        {"rings": 10e-6, "slab": 60e-6, "": 5e-6})
    [(op, ms)] = s.unscoped_ops("jit_step")
    assert op == "copy.5" and ms == pytest.approx(5e-6)
    run = _run(s)
    assert harness.load_metric("prober.slab_device_ms").read(run) is None
    s.calls = [(0, 100, "jit_estimate_batch_stats(77)")]
    assert harness.load_metric("prober.slab_device_ms").read(run) == \
        pytest.approx(60e-6)
    assert harness.load_metric("prober.central_device_ms").read(run) is None


def test_counter_readers():
    run = _run(None)
    for name in ("engine.syncs_per_flush", "engine.pad_lane_pct",
                 "engine.lookup_ms"):
        assert harness.load_metric(name).read(run) is None
    run.counters = {"flushes": 4, "syncs": 28, "probe_lanes": 16,
                    "probe_live": 12}
    assert harness.load_metric("engine.syncs_per_flush").read(run) == 7.0
    assert harness.load_metric("engine.pad_lane_pct").read(run) == 25.0


def test_old_trace_readers_pinned(tmp_path):
    """On the trace recorded before the program had spans, the readers the
    benchmark already had read the same values from either reduction."""
    path = _unzip(OLD, tmp_path)
    pinned = {"engine.flush_ms": 409.778914,
              "engine.host_pct": 8.738759066553625,
              "cache.hit_pct": 25.0,
              "prober.device_ms": 19.24522066666667,
              "prober.visits": 15.0,
              "device.idle_pct": 11.01871473698729}
    for summary in (trace.summarize(path), phases.summarize(path)):
        run = _run(summary, nvisited=[10, 20],
                   cache_stats={"hits": 1, "lookups": 4})
        got = {m: harness.load_metric(m).read(run) for m in OLD_READERS}
        assert got == pytest.approx(pinned, rel=1e-12, abs=0)
    s = phases.summarize(path)
    # 93 programs started on the device inside each flush of that trace
    assert s.launches_in("bench/flush") == [93, 93, 93]
    assert s.breakdown()["idle_gaps"] == trace.summarize(path).breakdown()[
        "idle_gaps"]


def test_new_trace_every_reader(tmp_path):
    """The small trace recorded with the program's own marks gives every
    phase metric: 7 reads and 60 programs per flush, idle time inside
    flushes named by their phases, the probe's device time by scope."""
    hlo = phases.hlo_modules(
        gzip.open(DATA / f"{SAMPLE}.hlo.txt.gz", "rt").read())
    assert [h.split(",")[0] for h in hlo] == \
        ["HloModule jit_estimate_batch_stats"] * 3
    s = phases.summarize(_unzip(NEW, tmp_path), hlo)
    run = _run(s)
    run.counters = json.loads((DATA / f"{SAMPLE}.counters.json").read_text())
    got = {m: harness.load_metric(m).read(run) for m in PHASE_METRICS}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["engine.syncs_per_flush"] == 7.0
    assert got["engine.pad_lane_pct"] == 0.0      # 8, 4, 2: no padding
    assert s.launches_in("coal/flush") == [60, 60, 60]
    idle = s.idle_by_phase("coal/flush")
    assert idle["coal/flush"] < 0.05 * sum(idle.values())
    assert len(s.span_durations_ms("bench/flush")) == 3
    assert "bench/flush" not in {name for name, _ in s.idle_gaps()}
    split = s.scope_ms(r"estimate_batch_stats")
    whole = np.mean(s.module_calls_ms(r"estimate_batch_stats"))
    probe = sum(split[k] for k in ("rings", "central", "slab"))
    assert split[""] < 0.1 * sum(split.values())
    assert abs(probe - whole) < 0.1 * whole
    assert any("/probe/slab/" in n for n, _ in s.breakdown()["device_ops"])


def test_profile_cell_at_tiny_size(tmp_path):
    """The profiling script serves and checks a cell as run.py does, reads
    the counter and span metrics (no device plane on the CPU), and writes
    its profile, HLO text and sample."""
    b = tiny.bench()
    cfg, mix = run.load_cell(b, "sift1m.uniform")
    mix["reads"]["rate_per_s"] = tiny.TINY_RATE
    metrics = harness.cell_metrics(b, "sift1m.uniform", "per_layer")
    out = profile_cell.profile(tiny.tiny(cfg), mix, 2 ** 33 + 7,
                               tiny.TINY_SECONDS, metrics, 0.0,
                               lambda s: None, out=tmp_path / "out",
                               sample=tmp_path / "sample")
    assert out["correct"], out["checks"]
    c = out["counters"]
    assert c["flushes"] > 0 and c["probe_lanes"] >= c["probe_live"]
    assert 0 < out["metrics"]["engine.syncs_per_flush"]["value"] <= 7
    assert "engine.lookup_ms" in out["metrics"]
    assert "engine.programs_per_flush" not in out["metrics"]
    for f in ("window.xplane.pb.gz", "window.hlo.txt.gz", "result.json"):
        assert (tmp_path / "out" / f).stat().st_size > 0
    sample = json.loads(
        (tmp_path / "sample" / f"{SAMPLE}.counters.json").read_text())
    assert sample["flushes"] == 3 and sample["syncs"] == 21
