"""The reduction from a profiler trace to per-layer numbers, on device
operations made by hand (a CPU trace has no device plane)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from perfbench import manifest, trace  # noqa: E402

def _device(ops, window):
    ops = [trace.Op(n, p, s, d) for n, p, s, d in ops]
    busy = trace._union_ns((o.start, o.start + o.dur) for o in ops)
    return trace.Device("/device:TPU:0", ops, busy, window)


def test_busy_time_is_the_union_of_overlapping_ops():
    dev = _device([("a", "", 0, 10), ("b", "", 5, 10), ("c", "", 30, 5)],
                  (0, 50))
    assert dev.busy_ns == 20
    gaps = trace.idle_gaps(dev, [("host work", 16, 29)])
    assert gaps == [["host work", 15e-9], ["none", 15e-9]]


@pytest.mark.parametrize("path, scope", [
    ("jit(run)/megakernel.demand/while/body/stage_scheduler/cumsum",
     "stage_scheduler"),
    ("jit(run)/megakernel.demand/while/body/add", "megakernel.demand"),
    ("jit(run)/jit(fused_facility_totals)/megakernel.facility.pallas/x",
     "megakernel.facility.pallas"),
    ("jit(run)/concatenate", "other"),
])
def test_an_op_counts_under_the_innermost_scope_it_names(path, scope):
    assert trace.innermost(path) == scope


def test_scope_time_is_inclusive_and_exact_by_component():
    dev = _device([
        ("f1", "jit/megakernel.demand/stage_scheduler", 0, 4),
        ("f2", "jit/megakernel.demand/stage_progress", 4, 3),
        ("f3", "jit/megakernel.facility.pallas", 7, 2),
        ("f4", "jit/megakernel.facility", 9, 1),
        ("f5", "jit/other_thing", 10, 5)], (0, 20))
    assert trace.scope_ns(dev, "megakernel.demand") == 7
    assert trace.scope_ns(dev, "stage_scheduler") == 4
    assert trace.scope_ns(dev, "megakernel.facility") == 3
    assert trace.scope_ns(dev, "megakernel.facility.pallas") == 2
    assert trace.top_ops(dev, 2) == [["other:f5", 5e-9],
                                     ["stage_scheduler:f1", 4e-9]]


def test_a_trace_without_a_device_reads_nothing():
    run = manifest.RunData(trace.Trace([], []), 0.5, None, "cpu", 1, 96)
    for name in ("idle_share_pct", "peak_hbm_mb"):
        assert manifest.reader(name, ROOT).read(run) is None


# One traced call of `simulate` over SURF cut to 8 hosts and 1 day, recorded
# on a TPU v5e by perfbench/record_trace.py.
RECORDED = ROOT / "perfbench" / "tests" / "surf_tiny.xplane.pb.gz"


def test_a_recorded_tpu_trace_reduces_to_its_device_numbers():
    tr = trace.load(str(RECORDED))
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    dev = tr.devices[0]
    assert len(dev.ops) == 12780
    assert dev.busy_ns == 4420314
    assert trace.window_ns(dev) == 8298239
    run = manifest.RunData(tr, 0.1, 24531968, "TPU v5 lite", 1, 96)
    idle = manifest.reader("idle_share_pct", ROOT).read(run)
    assert idle == pytest.approx(100 * (1 - 4420314 / 8298239))
    assert trace.top_ops(dev, 2) == [["other:while", 0.007377348],
                                     ["other:fusion", 0.001000176]]
    # the device operations carry no scope metadata here: every one is
    # `other`, which is why no per-scope metric is read yet
    assert all(trace.innermost(o.path) == "other" for o in dev.ops)
    gaps = trace.idle_gaps(dev, tr.host, 1)
    assert gaps == [["$api.py:3097 block_until_ready", 0.002064688]]
