"""Plan-advisor memo lifecycle (ISSUE 17).

The contract under test: per-template plan memos are LRU-bounded under
template churn (evictions counted, most-recent survive), advice decays
toward the static defaults when a template's measurements drift (the
drift cooldown stands every decision down until the signal re-converges),
``SET useAdvisor=false`` has ZERO memo effect (no reads, no writes,
bit-exact results against advisor-on), confirming decisions never stamp
an ``ADVISOR(...)`` line, and memo updates are thread-safe under the
PR-2 concurrent-launch path.
"""

import threading

import numpy as np
import pytest

from pinot_tpu.common.datatypes import DataType
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import IndexingConfig, TableConfig
from pinot_tpu.engine.advisor import PlanAdvisor, advisor_enabled
from pinot_tpu.engine.engine import QueryEngine
from pinot_tpu.storage.creator import build_segment
from pinot_tpu.storage.segment import ImmutableSegment

# ---------------------------------------------------------------------------
# unit: memo store
# ---------------------------------------------------------------------------


def test_lru_eviction_under_template_churn():
    adv = PlanAdvisor(max_memos=4, min_samples=2)
    for i in range(10):
        adv.observe(f"tpl{i}", skip_ratio=0.5)
    assert len(adv) == 4
    assert adv.evictions == 6
    # the most recently observed templates survive; the churned-out
    # oldest are gone
    assert adv.peek("tpl9") is not None
    assert adv.peek("tpl0") is None
    # touching a survivor protects it from the next eviction wave
    adv.observe("tpl6", skip_ratio=0.5)
    adv.observe("tplA", skip_ratio=0.5)
    assert adv.peek("tpl6") is not None
    assert adv.peek("tpl7") is None


def test_advice_needs_min_samples():
    adv = PlanAdvisor(min_samples=3)
    adv.observe("t", skip_ratio=0.9)
    adv.observe("t", skip_ratio=0.9)
    frac, note = adv.advise_blockskip("t", 16)
    assert (frac, note) == (16, None)  # still cold: default, no stamp
    assert adv.convergence("t") == "cold"
    adv.observe("t", skip_ratio=0.9)
    frac, note = adv.advise_blockskip("t", 16)
    assert frac == 0 and "ADVISOR(blockSkip=dense" in note
    assert adv.convergence("t") == "converged"


def test_confirming_decision_does_not_stamp():
    adv = PlanAdvisor(min_samples=2)
    for _ in range(3):
        adv.observe("t", build_rows={"d": 100})
    # measured 100 <= threshold confirms the BROADCAST default
    strat, note = adv.advise_join_strategy("t", "BROADCAST", "d", 1000)
    assert (strat, note) == ("BROADCAST", None)
    assert adv.peek("t").decisions == 1
    assert adv.peek("t").overrides == 0
    # ...and overrides it once the measurement says otherwise
    for _ in range(4):
        adv.observe("t", build_rows={"d": 50_000})
    strat, note = adv.advise_join_strategy("t", "BROADCAST", "d", 1000)
    assert strat == "SHUFFLE" and "ADVISOR(joinStrategy=SHUFFLE" in note


def test_drift_decays_advice_toward_default():
    adv = PlanAdvisor(min_samples=3)
    for _ in range(4):
        adv.observe("t", skip_ratio=0.01)
    frac, note = adv.advise_blockskip("t", 16)
    # 0.01 * CAND_HEADROOM fits under 1/32 but not 1/64
    assert frac == 32 and "ADVISOR(candBound=1/32" in note
    # the table's shape drifts: selectivity jumps past the drift factor
    adv.observe("t", skip_ratio=1.0)
    assert adv.convergence("t") == "drifting"
    frac, note = adv.advise_blockskip("t", 16)
    assert (frac, note) == (16, None)  # advice stands down to the default
    # consistent re-measurement re-converges and advice resumes — now
    # reflecting the NEW reality (non-selective => dense)
    for _ in range(8):
        adv.observe("t", skip_ratio=1.0)
    assert adv.convergence("t") == "converged"
    frac, note = adv.advise_blockskip("t", 16)
    assert frac == 0 and "blockSkip=dense" in note


def test_trim_advice_no_drop_rule():
    adv = PlanAdvisor(min_samples=2)
    for _ in range(3):
        adv.observe("t", groups=900)
    trim, note = adv.advise_trim("t", 5000)
    # pow2 >= 900 * 1.5 headroom: tightened but never below the observed
    # high-water group count
    assert trim == 2048 and "ADVISOR(groupTrim=2048" in note
    # an overflow observation (advised keep < actual groups) resets the
    # signal: advice stands down
    adv.observe("t", groups=4000, trim_keep=2048)
    assert adv.peek("t").trim_overflows == 1
    trim, note = adv.advise_trim("t", 5000)
    assert (trim, note) == (5000, None)


def test_dense_blockskip_advice_reprobes():
    adv = PlanAdvisor(min_samples=2, reprobe_every=4)
    for _ in range(3):
        adv.observe("t", skip_ratio=1.0)
    picks = [adv.advise_blockskip("t", 16)[0] for _ in range(8)]
    # mostly dense, but every reprobe_every-th decision returns the
    # default so the (skip-path-only) ratio stays measurable
    assert 16 in picks and picks.count(0) >= 5


def test_observe_thread_safety():
    adv = PlanAdvisor(max_memos=8, min_samples=2)
    n_threads, n_iter = 8, 300
    errors = []

    def work(t):
        try:
            for i in range(n_iter):
                key = f"tpl{(t + i) % 12}"
                adv.observe(key, skip_ratio=0.3, groups=50 + i % 7,
                            build_rows={"d": 1000 + i})
                adv.advise_blockskip(key, 16)
                adv.advise_trim(key, 5000)
                adv.snapshot()
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert adv.observations == n_threads * n_iter
    assert len(adv) <= 8


def test_advisor_enabled_option_parsing():
    assert advisor_enabled({}) is True
    assert advisor_enabled({"useadvisor": "false"}) is False
    assert advisor_enabled({"useadvisor": "'false'"}) is False  # quoted
    assert advisor_enabled({"useadvisor": "true"}) is True
    assert PlanAdvisor.from_config() is not None


# ---------------------------------------------------------------------------
# integration: the engine loop
# ---------------------------------------------------------------------------

ROWS = 8_192  # ZONE_BLOCK_ROWS-aligned: block-skip eligible


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    rng = np.random.default_rng(61)
    schema = Schema.build(
        name="adv",
        dimensions=[("ts", DataType.LONG)],
        metrics=[("m", DataType.INT)])
    cfg = TableConfig(
        table_name="adv",
        indexing=IndexingConfig(no_dictionary_columns=["ts"]))
    base = tmp_path_factory.mktemp("advisor")
    out = []
    for i in range(2):
        build_segment(
            schema,
            {"ts": (np.int64(i) * ROWS
                    + np.arange(ROWS, dtype=np.int64)),
             "m": rng.integers(0, 100, ROWS).astype(np.int32)},
            str(base / f"s{i}"), cfg, f"s{i}")
        out.append(ImmutableSegment(str(base / f"s{i}")))
    return out


@pytest.fixture(scope="module")
def engine(segs):
    eng = QueryEngine()
    for s in segs:
        eng.add_segment("adv", s)
    return eng


def _sql(i):
    # non-selective zone-prunable range: every block matches, so the
    # advisor learns ratio 1.0 and advises the dense form
    return (f"SET usePartialsCache = false; "
            f"SELECT COUNT(*), SUM(m) FROM adv "
            f"WHERE ts BETWEEN 0 AND {10 * 2 * ROWS + i}")


def test_use_advisor_false_zero_memo_effect_and_bit_exact(engine):
    advisor = engine.device.advisor
    assert advisor is not None and len(advisor) == 0
    # advisor-off queries: no reads, NO writes — the memo store stays
    # empty no matter how many run
    off_rows = None
    for i in range(4):
        r = engine.execute(f"SET useAdvisor = false; {_sql(i)}")
        assert not r["exceptions"]
        assert "advisorDecisions" not in r
        off_rows = r["resultTable"]["rows"]
    assert len(advisor) == 0
    # advisor-on training converges to the dense override...
    stamped_at = None
    for i in range(8):
        r = engine.execute(_sql(i))
        assert not r["exceptions"]
        assert r["resultTable"]["rows"] == off_rows  # bit-exact throughout
        if stamped_at is None and any(
                "ADVISOR(blockSkip=dense" in line
                for line in r.get("advisorDecisions") or ()):
            stamped_at = i
            break
    assert stamped_at is not None, "advisor never converged"
    assert len(advisor) == 1
    # ...and the advised (dense) execution stays bit-exact against a
    # fresh advisor-off twin
    twin = engine.execute(f"SET useAdvisor = false; {_sql(0)}")
    assert engine.execute(_sql(0))["resultTable"]["rows"] \
        == twin["resultTable"]["rows"]


def test_memo_updates_safe_under_concurrent_launches(engine):
    errors = []
    results = []
    barrier = threading.Barrier(6)

    def work(t):
        try:
            barrier.wait(timeout=30)
            for i in range(4):
                r = engine.execute(_sql(100 + t * 10 + i))
                assert not r["exceptions"]
                results.append(r["resultTable"]["rows"])
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(6)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    # every concurrent launch computed the same (full-table) answer
    assert len({tuple(map(tuple, rows)) for rows in results}) == 1
    memo = engine.device.advisor.peek(
        next(iter(engine.device.advisor.snapshot()["templates"])))
    assert memo is not None and memo.executions > 0
