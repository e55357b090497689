"""bench.py caller contract (BASELINE.md; ISSUE 2 satellite; ISSUE 21):
no matter what happens to the backend, stdout's LAST line is one COMPACT
parseable JSON record (a full record once outgrew the capture window), the
bulky parts (layer tables, scaling inputs) live in the record FILE the
compact line points at, and the EXIT CODE says whether a measurement
landed: 0 with a value, non-zero with an error record — which carries no
earlier run's numbers. The compact line must name the chosen lowering
variant per tunable op (ops.variants), so a reader sees WHICH lowerings
produced a number."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env, timeout, rc=0):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert out.returncode == rc, (out.returncode, out.stderr[-1000:])
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert lines, out.stderr[-1000:]
    last = lines[-1]
    # the whole point of the compact line: it can never outgrow a capture
    # window (full records are multi-KB)
    assert len(last) < 2048, f"compact line is {len(last)} bytes"
    return json.loads(last)             # the driver's parse


def test_error_record_is_parseable_and_carries_measurements(tmp_path):
    """The failure path: exit code NON-ZERO, last line still the compact
    parseable record, and nothing in it (or in the record file) from
    any earlier run — a failure that looks like a measurement is worse
    than none."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_RECORD_PATH"] = str(tmp_path / "rec.json")
    # tiny budgets: the child is killed long before it could measure
    env.update(BENCH_TOTAL_DEADLINE_S="20", BENCH_CHILD_TIMEOUT_S="6",
               BENCH_ATTEMPTS="1", BENCH_BACKOFF_S="1")
    rec = _run(env, timeout=120, rc=1)
    assert rec["metric"] == "alexnet_train_samples_per_sec_per_chip"
    # ISSUE 5 satellite: the failure path ENDS with the compact record
    # and classifies itself — no probing null values
    assert rec["status"] == "failed"
    assert rec["value"] is None and "error" in rec
    assert "degraded" not in rec        # no smaller-batch second chance
    assert rec["record"] == env["BENCH_RECORD_PATH"]
    with open(rec["record"]) as f:
        full = json.load(f)
    assert "last_measured" not in full and "last_measured" not in rec
    assert full["value"] is None
    assert full["error"]            # untruncated error text lives here


def test_success_record_names_variants_and_merges_e2e(tmp_path):
    """ISSUE 2: the captured line carries the
    device-only headline, the e2e headline AND the chosen variant per
    tunable op; the full record file keeps the loader/device
    decomposition. Narrow-width smoke on XLA:CPU — the protocol, not the
    numbers, is under test."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_RECORD_PATH"] = str(tmp_path / "rec.json")
    env.update(BENCH_BATCH="8", BENCH_STEPS="1", BENCH_WINDOWS="1",
               BENCH_WIDTH="0.125", BENCH_E2E_WIDTH="0.125",
               BENCH_E2E_ATTACH_BATCH="8", BENCH_E2E_ATTACH_SAMPLES="32",
               BENCH_CHILD_TIMEOUT_S="300", BENCH_TOTAL_DEADLINE_S="560",
               BENCH_ATTEMPTS="1")
    rec = _run(env, timeout=580)
    assert rec["metric"] == "alexnet_train_samples_per_sec_per_chip"
    assert rec["status"] == "ok"
    assert rec["value"] > 0, rec
    # the acceptance bar: the last stdout line NAMES the chosen variant
    # per tunable op the measured step contained
    variants = rec["variants"]
    for op in ("lrn", "maxpool", "conv_stem", "dropout"):
        assert isinstance(variants.get(op), str) and variants[op], variants
    assert rec["e2e_value"] > 0, rec
    # sanity only: on a loaded CPU host the two tiny-smoke protocols can
    # time either side of each other (observed 1.55), so the bound just
    # catches unit mistakes, not overlap quality
    assert 0 < rec["e2e_overlap"] <= 5.0
    with open(rec["record"]) as f:
        full = json.load(f)
    assert full["device_only"]["value"] == rec["value"]
    e2e = full["e2e"]
    assert e2e["metric"] == "alexnet_e2e_samples_per_sec_per_chip"
    assert e2e["value"] == rec["e2e_value"]
    assert e2e["loader_samples_per_sec"] > 0
    assert e2e["device_only_same_protocol"] > 0
    # the e2e child trains through the SHARED DeviceFeed: its overlap
    # counters land in the record — uint8 on the wire, batches fed ahead
    feed = e2e["feed"]
    assert feed["uint8_wire"] is True
    assert feed["bytes_per_batch"] > 0 and feed["batches"] > 0
    assert full["fwd_layer_gflops_per_sample"]   # bulk stays in the file
    # ISSUE 7 satellite: the compact line carries the measured
    # tracing-overhead A/B, and the JSONL telemetry sink mirrors the
    # flush next to the record file
    assert "telemetry" in rec and "overhead_frac" in rec["telemetry"]
    jsonl = env["BENCH_RECORD_PATH"] + ".telemetry.jsonl"
    assert os.path.exists(jsonl)
    row = json.loads(open(jsonl).readline())
    assert row["metrics"]["veles_step_total"] > 0


@pytest.mark.slow
def test_telemetry_overhead_under_one_percent(tmp_path):
    """ISSUE 7 acceptance: measured tracing overhead < 1% of step time,
    A/B asserted. The bench child records span_pair cost with a LIVE
    tracer vs the disabled-path guard and relates 8 spans/step to the
    measured step time; on any host where a step takes >= ~10 ms (CPU
    smoke included) the tracer's ~1-2 us span pairs are orders of
    magnitude under the budget. Slow-marked: runs the real child."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_RECORD_PATH"] = str(tmp_path / "rec.json")
    env.update(BENCH_BATCH="8", BENCH_STEPS="2", BENCH_WINDOWS="1",
               BENCH_WIDTH="0.125", BENCH_HW="67", BENCH_CHILD="1")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-1000:]
    rec = json.loads([ln for ln in out.stdout.splitlines()
                      if ln.strip()][-1])
    tele = rec["telemetry"]
    assert tele["spans_per_step"] == 8
    assert tele["span_pair_us"] > 0
    # the A/B: tracing-on span cost vs the tracing-off guard, relative
    # to THIS run's measured step time
    assert tele["overhead_frac"] is not None
    assert tele["overhead_frac"] < 0.01, tele
