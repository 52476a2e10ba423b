"""The build fits its machine (PR 39): children are admitted by the memory
that is free, a child's peak is reckoned from the configuration, every
child but the oldest stands still while the machine is short and the oldest
never does, a dead run's work directory is swept and a live one's is not,
and a tiny ``build_table`` gives the same directories and reference rows as
before with its ``build`` line in bytes."""

import copy
import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

import run  # noqa: F401 - puts the program beside the harness on sys.path
from harness import machine, reference, spec, table

HERE = os.path.dirname(os.path.abspath(__file__))
GB = 10 ** 9


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _config(name):
    if name.startswith("ssb_tiny"):
        return _json(HERE, "testdata", name + ".json")
    return _json(spec.BENCH_DIR, "configs", name + ".json")


# ---- admission: the readings handed in -----------------------------------

@pytest.mark.parametrize("waiting,running,free,want", [
    (8, [], 8 * 3 * GB + 2 * GB, 8),          # all fit: all start at once
    (8, [], 8 * 3 * GB + 2 * GB - 1, 7),      # a byte short of the eighth
    (8, [], 5 * 3 * GB + 2 * GB + GB, 5),     # k at a time when k fit
    (8, [], 1 * GB, 1),                       # none fits: one runs anyway
    (8, [], 0, 1),
    (7, [1 * GB], 3 * GB, 0),                 # one runs, none fits beside it
    (3, [3 * GB] * 5, 2 * GB + 3 * 3 * GB, 3),  # grown children owe nothing
    (3, [1 * GB] * 5, 2 * GB + 3 * 3 * GB, 0),  # young ones are owed 2 GB each
    (3, [1 * GB] * 5, 2 * GB + 10 * GB + 2 * 3 * GB, 2),
    (12, [3 * GB] * 8, 100 * GB, 0),          # never more than the cores
    (12, [3 * GB] * 6, 100 * GB, 2),
    (0, [], 100 * GB, 0),
])
def test_admit(waiting, running, free, want):
    assert table.admit(waiting, running, free, child=3 * GB,
                       reserve=2 * GB, workers=8) == want


def test_a_squeezed_build_goes_k_at_a_time_and_ends():
    """Eight children of 3 GB on a machine with 11 GB free beside the
    reserve: never more than three at once, all eight built."""
    held, done, most = [], 0, 0
    waiting = 8
    while waiting or held:
        free = 11 * GB + 2 * GB - sum(held)
        n = table.admit(waiting, held, free, 3 * GB, 2 * GB, 8)
        waiting -= n
        held += [0] * n
        most = max(most, len(held))
        held = [h + GB for h in held]          # each grows a GB a tick
        done += sum(h > 3 * GB for h in held)
        held = [h for h in held if h <= 3 * GB]
    assert (done, most) == (8, 3)


# the chip's machine as step 0 read it (PERF.md s.7): 48.32 GB, 47.4-48.2
# of them free at a run's start, three or eight children by its 13 cores
@pytest.mark.parametrize("name,workers,least_free_gb", [
    ("ssbproxy_lineorder_100m", 8, 32.0),
    ("ssbproxy_lineorder_100m_bydate", 8, 32.0),
    ("ssb_sf100_chipshare", 3, 35.0),
    ("ssb_sf100_fullkeys", 3, 35.0),
])
def test_every_accepted_table_starts_all_its_children_at_once(
        name, workers, least_free_gb):
    """... with 12 GB and more to spare: down to ``least_free_gb`` free
    (a run that starts while the one before it is still being handed back)
    `setup_s` is what it was, and a GB under that one child waits."""
    child = table.child_peak_bytes(_config(name))
    reserve = int(48.32 * GB) // table.RESERVE_SHARE
    for free_gb, want in ((47.4, workers), (40.0, workers),
                          (least_free_gb, workers),
                          (least_free_gb - 1.0, workers - 1)):
        assert table.admit(workers, [], int(free_gb * GB), child, reserve,
                           workers) == want, free_gb


# ---- a child's reckoned peak ---------------------------------------------

@pytest.mark.parametrize("name,measured_gb", [
    ("ssbproxy_lineorder_100m_bydate", 2.68),
    ("ssbproxy_lineorder_100m", 2.68),
    ("ssb_sf100_chipshare", 7.6),
    ("ssb_sf100_fullkeys", 7.6),
])
def test_child_peak_is_near_what_was_measured(name, measured_gb):
    reckoned = table.child_peak_bytes(_config(name)) / GB
    assert 0.75 * measured_gb <= reckoned <= 1.25 * measured_gb


def test_child_peak_grows_with_rows_columns_and_string_length():
    config = _config("ssb_tiny")
    base = table.child_peak_bytes(config)
    more_rows = dict(config, rows_per_segment=2 * config["rows_per_segment"])
    assert table.child_peak_bytes(more_rows) == 2 * base
    wider = copy.deepcopy(config)
    wider["generator"].append({"column": "extra", "kind": "integers",
                               "low": 0, "high": 10})
    assert table.child_peak_bytes(wider) > base
    assert table.row_bytes(wider) == table.row_bytes(config) + 8
    longer = copy.deepcopy(config)
    entry = table.column_spec(longer, "c_region")
    entry["values"] = [v + "_AND_MORE" for v in entry["values"]]
    assert table.row_bytes(longer) \
        == table.row_bytes(config) + 4 * len("_AND_MORE")
    assert table.child_peak_bytes(longer) > base


# ---- what the machine has free --------------------------------------------

def test_free_memory_is_meminfos(tmp_path):
    path = os.path.join(str(tmp_path), "meminfo")
    with open(path, "w") as f:
        f.write("MemTotal:       1000 kB\nMemFree:         100 kB\n"
                "MemAvailable:   400 kB\nHugePages_Total:       0\n")
    assert machine.free_memory(path) == (400 * 1024, 1000 * 1024)
    assert machine.free_memory(os.path.join(str(tmp_path), "absent")) \
        == (0, 0)
    # the machine this test runs on answers too, whatever it has
    free, total = machine.free_memory()
    assert 0 < free <= total


# ---- a dead run's directory ----------------------------------------------

def test_sweep_takes_a_dead_runs_directory_and_leaves_a_live_ones(tmp_path):
    tmp = str(tmp_path)
    made = {}
    for name in ("live", "dead", "reused", "unclaimed_new", "unclaimed_old"):
        made[name] = os.path.join(tmp, "pinot_tpu_benchmark_" + name)
        os.makedirs(os.path.join(made[name], "server_0", "built"))
    os.makedirs(os.path.join(tmp, "something_else"))
    machine.claim(made["live"])                       # this process lives
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    child.wait()
    with open(os.path.join(made["dead"], machine.PID_FILE), "w") as f:
        f.write(f"{child.pid} 12345\n")
    with open(os.path.join(made["reused"], machine.PID_FILE), "w") as f:
        f.write(f"{os.getpid()} 1\n")                 # my pid, another life
    old = time.time() - machine.UNCLAIMED_STALE_S - 60
    os.utime(made["unclaimed_old"], (old, old))
    removed = machine.sweep_stale(tmp, "pinot_tpu_benchmark_")
    assert sorted(removed) == sorted(
        made[n] for n in ("dead", "reused", "unclaimed_old"))
    assert sorted(os.listdir(tmp)) == [
        "pinot_tpu_benchmark_live", "pinot_tpu_benchmark_unclaimed_new",
        "something_else"]
    assert machine.sweep_stale(os.path.join(tmp, "nowhere"), "x") == []


def test_tree_rss_holds_this_process_and_its_children():
    child = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(30)"])
    try:
        tree = machine.tree_rss(os.getpid())
        assert tree[os.getpid()] > 0 and tree[child.pid] > 0
    finally:
        child.kill()
        child.wait()
    assert child.pid not in machine.tree_rss(os.getpid())


# ---- a tiny build ---------------------------------------------------------

def _builders_of(pid):
    """The spawned children under ``pid`` (multiprocessing's resource
    tracker is one of its own and stays)."""
    out = []
    for p in machine.tree_rss(pid):
        try:
            with open(f"/proc/{p}/cmdline") as f:
                if "spawn_main" in f.read():
                    out.append(p)
        except OSError:      # gone since the tree was read
            pass
    return out


FIELDS = ("free_gb", "child_gb", "peak_tree_gb", "limit_gb", "waited_s")


def _build(tmp, name="ssb_tiny_bydate", seed=7):
    config = _config(name)
    statements = _json(spec.BENCH_DIR, "traffic",
                       "range_bands_c4.json")["statements"]
    ref = reference.Reference(config, statements)
    lines = []
    dirs = table.build_table(config, seed, os.path.join(tmp, "built"), ref,
                             lines.append)
    plain = reference.Reference(config, statements)
    for cols in table.reference_segments(config, seed, plain.columns):
        plain.add(cols)
    assert ref.rows() == plain.rows()
    assert dirs == [os.path.join(tmp, "built", f"s{k}")
                    for k in range(config["segments"])]
    for d in dirs:
        assert os.path.isfile(os.path.join(d, "metadata.json")), os.listdir(d)
    (line,) = lines
    said = dict(re.findall(r"(\w+)=(\S+)", line))
    assert line.startswith("build segments=8 ")
    return config, {k: float(said[k]) for k in FIELDS + ("workers",)}, said


def test_a_tiny_build_says_bytes(tmp_path):
    config, said, _ = _build(str(tmp_path))
    assert said["waited_s"] == 0.0
    assert said["child_gb"] == round(table.child_peak_bytes(config) / 1e9, 2)
    assert 0 < said["peak_tree_gb"] < said["limit_gb"]
    assert 0 < said["free_gb"] <= said["limit_gb"]


def test_a_squeezed_tiny_build_waits_and_builds_the_same(
        tmp_path, monkeypatch):
    """A machine with nothing free: the children go one by one, the build
    ends, and it gives what the unsqueezed one gives."""
    monkeypatch.setattr(machine, "free_memory", lambda: (0, 40 * GB))
    started = []
    sound = table._Crew._poll

    def watched(self, dt):
        sound(self, dt)
        started.append(len(self.running))

    monkeypatch.setattr(table._Crew, "_poll", watched)
    _, said, _ = _build(str(tmp_path))
    assert max(started) == 1
    assert said["waited_s"] > 0.0 and said["free_gb"] == 0.0


def test_children_stand_still_while_less_than_the_reserve_is_free(
        tmp_path, monkeypatch):
    """All eight start; then the machine runs short for a moment: every
    child but the oldest stands still, they go on one by one once memory is
    back, and the build gives what it always gives."""
    t0 = time.time()
    limit = 48 * GB

    def free_memory():
        short = 1.0 < time.time() - t0 < 3.0
        return (1 * GB if short else 47 * GB), limit

    monkeypatch.setattr(machine, "free_memory", free_memory)
    seen = []
    sound = table._Crew._poll

    def watched(self, dt):
        sound(self, dt)
        seen.append((len(self.running), len(self.held)))

    monkeypatch.setattr(table._Crew, "_poll", watched)
    _, said, _ = _build(str(tmp_path))
    assert seen[0] == (said["workers"], 0)      # all the cores allow, at once
    assert max(held for _, held in seen) == max(
        running for running, held in seen if held) - 1 > 0
    assert seen[-1][1] == 0
    assert said["waited_s"] >= 1.5


def test_the_oldest_child_never_stands_still_on_a_machine_that_stays_short(
        tmp_path, monkeypatch):
    """All start; then the machine runs short and stays short to the end
    (a real squeeze: nothing comes back). Every child but the oldest
    stands still, the oldest that lives never does, each goes on as the
    one before it ends, and the build ends with what it always gives."""
    calls = []

    def free_memory():
        calls.append(1)
        return (47 * GB if len(calls) <= 2 else 1 * GB), 48 * GB

    monkeypatch.setattr(machine, "free_memory", free_memory)
    seen = []
    sound = table._Crew._poll

    def watched(self, dt):
        sound(self, dt)
        seen.append((sorted(self.running), sorted(self.held)))

    monkeypatch.setattr(table._Crew, "_poll", watched)
    _, said, _ = _build(str(tmp_path))
    assert seen[0] == (list(range(int(said["workers"]))), [])
    squeezed = [(running, held) for running, held in seen[1:] if running]
    assert squeezed and max(len(held) for _, held in squeezed) > 0
    for running, held in squeezed:
        assert held == running[1:], (running, held)   # the oldest goes on
    assert seen[-1] == ([], [])
    assert said["waited_s"] > 0.0


def test_a_child_that_raises_fails_the_build_and_leaves_none(tmp_path):
    config = _config("ssb_tiny")
    blocked = os.path.join(str(tmp_path), "built")
    with open(blocked, "w") as f:      # a file where the directories go
        f.write("x")
    ref = reference.Reference(config, _json(
        spec.BENCH_DIR, "traffic", "groupby_bands_c4.json")["statements"])
    with pytest.raises(RuntimeError, match="segment \\d's child raised"):
        table.build_table(config, 7, blocked, ref, lambda line: None)
    assert _builders_of(os.getpid()) == []


# ---- a run that is stopped ------------------------------------------------

_DRIVE = """
import os, signal, sys
sys.path.insert(0, {bench!r})
import run
signal.signal(signal.SIGTERM, run._terminated)
def look(chips):
    import jax
    return jax.devices()[:chips]
run.run(run.parse(["--workload", "tiny_bydate.range_sum", "--seed", "7",
                   "--seconds", "2", "--benchmark-json", {tiny!r}]), look)
"""


@pytest.mark.parametrize("how", ["SIGTERM", "SIGKILL"])
def test_a_stopped_run_leaves_nothing_for_the_next(tmp_path, how):
    tmp = str(tmp_path)
    env = dict(os.environ, TMPDIR=tmp, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", _DRIVE.format(
            bench=spec.BENCH_DIR,
            tiny=os.path.join(HERE, "testdata", "benchmark_tiny.json"))],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 120
        builders = []
        while time.time() < deadline and len(builders) < 2:
            time.sleep(0.2)
            builders = _builders_of(proc.pid)
            assert proc.poll() is None, "the run ended before its build"
        (work,) = [n for n in os.listdir(tmp)
                   if n.startswith("pinot_tpu_benchmark_")]
        proc.send_signal(getattr(signal, how))
        rc = proc.wait(timeout=60)
    finally:
        proc.kill()
        proc.wait()
    deadline = time.time() + 30
    while time.time() < deadline and any(
            machine._stat(p) for p in builders):
        time.sleep(0.2)
    assert not [p for p in builders if machine._stat(p)], \
        "children outlived the run"
    if how == "SIGTERM":
        assert rc == 128 + signal.SIGTERM
        assert os.listdir(tmp) == []
    else:
        assert rc == -signal.SIGKILL
        assert os.listdir(tmp) == [work]          # no `finally` was reached
        assert machine.sweep_stale(tmp, "pinot_tpu_benchmark_") \
            == [os.path.join(tmp, work)]
        assert os.listdir(tmp) == []
