"""A whole run at a tiny size on the CPU, the look for a chip skipped: sound
as it stands, and ``correct`` false with the timed path broken underneath —
an answer altered where the broker hands it out, a caller handed the answer
to another caller's statement, and a segment of the table left out of what
the server holds."""

import os

import pytest

import run as run_mod
from harness import cluster as cluster_mod

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "testdata", "benchmark_tiny.json")


def _look(chips):
    import jax

    return jax.devices()[:chips]


def _run(workload, seed):
    args = run_mod.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", "2", "--trace", "0",
                          "--benchmark-json", TINY])
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


@pytest.mark.parametrize("workload", ["tiny.groupby_scan",
                                      "tiny_bydate.range_sum"])
@pytest.mark.parametrize("fault", [None, _alter_an_answer,
                                   _hand_over_anothers_answer,
                                   _leave_a_segment_out])
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
        assert "off_device" not in numbers  # an untraced run has no span
    else:
        assert not result["correct"]
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
