"""A whole run at a tiny size on the CPU, the look for a chip skipped: sound
as it stands, and ``correct`` false with the timed path broken underneath —
an answer altered where the broker hands it out, a caller handed the answer
to another caller's statement, a segment of the table left out of what the
server holds, and the device statements answered by the host executor. Also
SSB flat at its tiny size, from a BENCHMARK file the test writes: a
configuration is files and entries."""

import json
import os

import pytest

import run as run_mod
from harness import cluster as cluster_mod

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "testdata", "benchmark_tiny.json")


def _look(chips):
    import jax

    return jax.devices()[:chips]


def _run(workload, seed, benchmark_json=TINY):
    args = run_mod.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", "2", "--trace", "0",
                          "--benchmark-json", benchmark_json])
    return run_mod.run(args, _look)


def _alter_an_answer(monkeypatch):
    from pinot_tpu.broker.broker import Broker

    sound = Broker.execute

    def altered(self, sql, *a, **kw):
        resp = sound(self, sql, *a, **kw)
        rows = (resp.get("resultTable") or {}).get("rows")
        if rows:
            rows[0][-1] += 1
        return resp

    monkeypatch.setattr(Broker, "execute", altered)


def _hand_over_anothers_answer(monkeypatch):
    """What a coalesced launch that mixed up its slots would do: every
    statement is answered, exactly, with the rows of the one before it."""
    from pinot_tpu.broker.broker import Broker

    sound = Broker.execute
    last = {}

    def swapped(self, sql, *a, **kw):
        resp = sound(self, sql, *a, **kw)
        table = resp.get("resultTable") or {}
        mine = table.get("rows")
        if mine and last.get("sql", sql) != sql:
            table["rows"] = last["rows"]
        if mine:
            last.update(sql=sql, rows=mine)
        return resp

    monkeypatch.setattr(Broker, "execute", swapped)


def _leave_a_segment_out(monkeypatch):
    # the second: by date it holds rows of 1992 and 1993
    sound = cluster_mod.Cluster.load
    monkeypatch.setattr(
        cluster_mod.Cluster, "load",
        lambda self, dirs, say: sound(self, dirs[:1] + dirs[2:], say))


def _answer_on_the_host(monkeypatch):
    """What a group-by whose key space passes the device's table does: once
    the warm-up is over every launch is refused, the host executor answers,
    exactly, and nothing errs."""
    from pinot_tpu.engine.device import DeviceExecutor
    from pinot_tpu.engine.params import DeviceUnsupported

    sound_launch, sound_check = DeviceExecutor.launch, run_mod.check_counters
    refusing = []

    def launch(self, *a, **kw):
        if refusing:
            raise DeviceUnsupported("refused by the test")
        return sound_launch(self, *a, **kw)

    def check_counters(cluster, where):  # the last check before the window
        sound_check(cluster, where)
        refusing.append(where)

    monkeypatch.setattr(DeviceExecutor, "launch", launch)
    monkeypatch.setattr(run_mod, "check_counters", check_counters)


@pytest.mark.parametrize("workload", ["tiny.groupby_scan",
                                      "tiny_bydate.range_sum"])
@pytest.mark.parametrize("fault", [None, _alter_an_answer,
                                   _hand_over_anothers_answer,
                                   _leave_a_segment_out,
                                   _answer_on_the_host])
def test_run(monkeypatch, workload, fault):
    if fault:
        fault(monkeypatch)
    result = _run(workload, 4_000_000_019)
    numbers = result["compared"]
    assert result["attempted"] == numbers["answers_compared"]["value"] > 0
    if fault is None:
        assert result["correct"] and result["failed"] == 0
        assert set(result["metrics"]) == {"queries_per_s", "query_p50_ms",
                                          "query_p95_ms", "setup_s"}
        assert numbers["off_device"] == {"value": 0, "limit": 0}
    elif fault is _answer_on_the_host:
        # every answer is right and none came from the device: only the
        # always-on count of the untraced responses says so
        assert not result["correct"] and result["failed"] == 0
        assert numbers["off_device"]["value"] == result["attempted"]
    else:
        assert not result["correct"]
        assert numbers["off_device"]["value"] == 0
        wrong = numbers["answers_wrong"]["value"]
        assert result["failed"] == wrong
        if fault is _alter_an_answer or (
                fault is _leave_a_segment_out and "bydate" not in workload):
            assert wrong == result["attempted"]
        else:
            # some answers stay right: a caller that sends one statement
            # twice in a row, a year that the segment left out holds no
            # row of
            assert 0 < wrong <= result["attempted"]
    assert list(result)[-1] == "compared"


def test_a_configuration_is_files_and_entries(tmp_path):
    """SSB flat joins the two tiny cells by a configuration file, a traffic
    file and two entries of a BENCHMARK file: no file of the harness names
    its kinds, its operators or its columns. All 13 statements are sent:
    the program answers each of them on the device path at this size (the
    seed is one at which no flight is empty)."""
    with open(TINY) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "ssb_flat_tiny", "source": "test", "reduced": [],
        "file": "benchmark/harness/testdata/ssb_flat_tiny.json",
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny_ssbflat.13q", "config": "ssb_flat_tiny",
        "traffic": "ssb_flat_13q_c4", "chips": 1, "why": "test"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    result = _run("tiny_ssbflat.13q", 4_000_000_013, str(path))
    numbers = result["compared"]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == numbers["answers_compared"]["value"] >= 13
    assert not any(numbers[k]["value"] for k in (
        "answers_wrong", "answers_missing", "off_device", "device_failures"))
