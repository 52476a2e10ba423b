"""The table of a configuration: seeded column generator, segment layout,
and the spawned children that turn each segment's columns into segment
files with the program's ``build_segment``.

The generator is data: a configuration's ``generator`` lists one entry per
column, each of a ``kind`` that is a file of ``benchmark/generators/`` found
by that name, and the columns are made in that order, a later one from the
earlier ones where its kind derives it (the first three kinds are a copy of
``pinot_tpu/tools/ssb.py segment_columns``). No kind is named here. Segment
``k`` has a generator of its own,
``numpy.random.default_rng([seed, k])``, so that all children start at once
instead of waiting for the parent to draw through the segments before
theirs. Nothing here touches JAX; the children pin it to the CPU before the
package (whose ``__init__`` imports jax) loads.

The build reads the machine it runs on (``harness/machine.py``) and never
asks it for more than is free: at most ``cores - 1`` children at once, and
the next one only while what is free now covers its reckoned peak, what the
running ones have yet to take, and a reserve (``admit``). Where all fit
they all start at once. What the machine charges a build is more than its
processes hold (the chip's machine returns freed memory tens of seconds
late: PERF.md s.7), so the reckoning is not enough: while less than the
reserve is free every child but the oldest stands still (``SIGSTOP``), and
they go on one by one as memory comes back. The oldest never stands still.
"""

from __future__ import annotations

import ctypes
import multiprocessing
import os
import signal
import threading
import time
import traceback

import numpy as np

from . import machine
from . import spec as spec_mod

# What a building child holds at its peak, reckoned from the configuration
# alone: rows a segment x (working bytes a row + FACTOR x the generated
# columns' bytes a row as numpy holds their values, ``row_bytes``). Fitted to
# the children of 12,500,000 rows on the chip's machine (the `build` line's
# `child_max_gb`: PERF.md s.6, PR 39):
#   a child of the 9-column table (120 B a row) peaks at 2.97 GB, in the
#     star-tree pass (2.68 GB on ISSUE 39's machine): 135 + 0.87 x 120 =
#     239 B a row, 2.99 GB;
#   a child of the 30-column table (668 B a row) at 8.84-8.87 GB, holding
#     its columns (7.6 GB at PR 32): 135 + 0.87 x 668 = 716 B a row, 8.95 GB.
# One factor alone cannot hold both children (2.97 / 1.50 GB = 1.98, 8.87 /
# 8.35 GB = 1.06): the narrow table's peak is its star-tree pass, which does
# not grow with the columns' widths: two constants through two readings, so
# read a third table shape's `child_max_gb` before trusting them for it. A
# reckoning that is too low is the brake's to catch; one that is too high
# costs a wait, and only where less is free than all the children's peaks.
# It is the one number admission reckons with: this process's fold of the
# reference's columns (0.9-11.5 GB, the `build` line's `fold_max_gb`) is the
# reserve's and the brake's to cover.
FACTOR = 0.87
CHILD_ROW_BYTES = 135
# kept free beside the reckoned peaks: a sixth of the machine's memory (the
# chip's machine has 48.3 GB and its keeper, which no file shows, ends a run
# at 40 GiB = 42.9 GB used, 5.4 GB short of all of it; a build has come
# within 4.3 GB of that end), and never under 2 GB
RESERVE_SHARE = 6
RESERVE_FLOOR_BYTES = 2_000_000_000
# what is free falls by up to 1.5 GB a second while eight children grow
POLL_S = 0.25


def column_spec(config: dict, column: str) -> dict:
    for spec in config["generator"]:
        if spec["column"] == column:
            return spec
    raise KeyError(f"configuration {config['name']} generates no {column!r}")


def kind_of(spec: dict):
    """The file that says how an entry's column is drawn and what its
    domain is: ``benchmark/generators/<kind>.py`` (its parts are listed in
    ``generators/integers.py``)."""
    return spec_mod.found("generators", spec["kind"])


def check_generator(config: dict) -> None:
    """Every kind the configuration names is found and has looked its entry
    over, before a row is drawn or a child is started."""
    for spec in config["generator"]:
        kind = kind_of(spec)
        if hasattr(kind, "check"):
            kind.check(spec)


def domain_size(config: dict, column: str) -> int:
    """How many distinct values a generated column can take."""
    spec = column_spec(config, column)
    return kind_of(spec).domain_size(spec)


def segment_rng(seed: int, k: int) -> np.random.Generator:
    return np.random.default_rng([seed, k])


def _wanted(config: dict, only) -> tuple:
    """(the columns to hand back, those to materialise for them): a
    column a wanted one is derived from is made too, and a ``helper`` is
    made for others and handed to nobody."""
    back = {s["column"] for s in config["generator"]
            if not s.get("helper") and (only is None or s["column"] in only)}
    made = set(back)
    for spec in reversed(config["generator"]):
        kind = kind_of(spec)
        if spec["column"] in made and hasattr(kind, "needs"):
            made.update(kind.needs(spec))
    return back, made


def draw_segment(config: dict, rng: np.random.Generator, only=None,
                 seed: int = 0, k=None) -> dict:
    """One segment's columns in generated order. Every draw is made, so
    that a column's values do not depend on which others are asked for;
    ``only`` names the columns worth materialising. A kind may derive its
    column from those before it and from ``seed`` (a dimension table is the
    same in every segment of a run). With ``k`` the layout column is re-mapped
    to segment ``k``'s share of its domain as soon as it is drawn
    (``by_date``), so that what is derived from it follows."""
    n = config["rows_per_segment"]
    back, made = _wanted(config, only)
    layout = config["layout"]
    cols = {}
    for spec in config["generator"]:
        name, kind = spec["column"], kind_of(spec)
        raw = kind.draw(spec, rng, n) if hasattr(kind, "draw") else None
        if name not in made:
            continue
        cols[name] = kind.column(spec, raw, cols, seed)
        if k is not None and layout["kind"] != "generated" \
                and name == layout["column"]:
            cols[name] = _remap(config, k, cols[name])
    return {name: v for name, v in cols.items() if name in back}


def _layout_share(config: dict, k: int):
    """(the layout column's entry, its kind, the first domain index of
    segment ``k``, the first of segment ``k+1``): S segments cut the domain
    into S runs of whole values, equal where S divides it."""
    layout = config["layout"]
    if layout["kind"] != "by_date":
        raise ValueError(f"unknown layout {layout['kind']!r}")
    spec = column_spec(config, layout["column"])
    kind = kind_of(spec)
    size, s = kind.domain_size(spec), config["segments"]
    if size < s:
        raise ValueError("by_date needs a date value a segment at least")
    return spec, kind, k * size // s, (k + 1) * size // s


def segment_range(config: dict, k: int, column: str):
    """(lowest, highest) value ``column`` can take in segment ``k``, or None
    where the layout does not narrow it: the layout column itself, and a
    column derived from it alone (a date's year), read off the segment's
    share of the dates."""
    layout = config["layout"]
    if layout["kind"] == "generated":
        return None
    spec, kind, lo, hi = _layout_share(config, k)
    dates = kind.value_of(spec, np.arange(lo, hi))
    if column != layout["column"]:
        derived = column_spec(config, column)
        of = kind_of(derived)
        if hasattr(of, "draw") or not hasattr(of, "needs") \
                or list(of.needs(derived)) != [layout["column"]]:
            return None
        dates = of.column(derived, None, {layout["column"]: dates}, 0)
    distinct = np.unique(dates)  # sorts strings too, where min() cannot
    return distinct[0].item(), distinct[-1].item()


def _remap(config: dict, k: int, values):
    """by_date: segment ``k`` of S keeps its rows, and a value at index i
    of the N in the layout column's domain moves to index ``lo + i * (hi -
    lo) // N`` of the segment's own share [lo, hi) — ``per_seg*k + i // S``
    where S divides N. The answer to a statement does not depend on row
    order, so this is all the reference needs."""
    spec, kind, lo, hi = _layout_share(config, k)
    size = kind.domain_size(spec)
    # the domain is small: where each of its values moves to, then a gather
    moved = kind.value_of(spec, lo + np.arange(size) * (hi - lo) // size)
    return moved.astype(values.dtype)[kind.index_of(spec, values)]


def reference_segments(config: dict, seed: int, only):
    """Each segment's columns as the reference needs them: drawn from the
    segment's own generator, the layout column re-mapped, rows unsorted."""
    for k in range(config["segments"]):
        yield draw_segment(config, segment_rng(seed, k), only=only,
                           seed=seed, k=k)


def lay_out(config: dict, cols: dict) -> dict:
    """The segment as it is stored: by_date stable-sorts the rows by the
    layout column, which ``draw_segment`` re-mapped."""
    if config["layout"]["kind"] == "generated":
        return cols
    order = np.argsort(cols[config["layout"]["column"]], kind="stable")
    for name in cols:
        # one column at a time: a second copy of the whole segment in each
        # of eight children does not fit the machine beside the build
        cols[name] = cols[name][order]
    return cols


class _HandOver(dict):
    """A mapping that gives each column away once. ``build_segment`` reads
    a column, writes its files and moves on; its star-tree pass, where a
    child's memory peaks, reads the sealed files. Handing the arrays over
    instead of keeping them takes 1.1 GB off that peak in each of eight
    children, which a 40 GiB machine does not have to spare."""

    def __getitem__(self, name):
        return self.pop(name)


def row_bytes(config: dict) -> int:
    """Bytes a row of the generated columns as numpy holds their values:
    8 B a whole number, 4 B a character of a string column's longest value.
    Read off each kind's ``value_of``."""
    total = 0
    for spec in config["generator"]:
        dtype = kind_of(spec).value_of(spec, np.arange(1)).dtype
        total += 8 if dtype.kind in "iu" else dtype.itemsize
    return total


def child_peak_bytes(config: dict) -> int:
    """What one building child holds at its peak (the constants above)."""
    return int(config["rows_per_segment"]
               * (CHILD_ROW_BYTES + FACTOR * row_bytes(config)))


def admit(waiting: int, running_rss, free: int, child: int, reserve: int,
          workers: int) -> int:
    """How many of the ``waiting`` children to start now. ``running_rss`` is
    what each running child holds, ``free`` what the machine has free at this
    moment, ``child`` a child's reckoned peak, ``reserve`` what has to stay
    free beside the children. A running child is owed what it has not yet
    grown to; the next one starts only while the rest covers its whole
    peak. One child always runs, however little is free: a build that does
    not fit goes one by one, it does not die."""
    owed = sum(max(0, child - held) for held in running_rss)
    fit = (free - reserve - owed) // child
    n = max(0, min(waiting, workers - len(running_rss), fit))
    return 1 if waiting and not n and not running_rss else n


def _die_with_parent(parent: int) -> None:
    """A child whose parent is gone (``kill -9`` reaches no ``finally``)
    has nobody to build for: without this it works on for half a minute,
    holding its gigabytes and writing its files into a directory nobody
    removes. Linux ends it with the parent, even where it stands still; the
    loop is for a parent that was gone before that was asked for."""
    try:
        prctl = ctypes.CDLL(None, use_errno=True).prctl
        prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
        prctl.restype = ctypes.c_int
        prctl(1, signal.SIGKILL, 0, 0, 0)      # PR_SET_PDEATHSIG
    except (OSError, AttributeError):
        pass
    while os.getppid() == parent:
        time.sleep(1.0)
    os._exit(1)


def _child_main(conn, parent: int, job) -> None:
    threading.Thread(target=_die_with_parent, args=(parent,),
                     daemon=True).start()
    try:
        conn.send(("ok", _build_segment_job(job)))
    except BaseException:  # noqa: BLE001 - the parent raises it
        conn.send(("error", traceback.format_exc()))
        raise


class _Crew(threading.Thread):
    """The building children, run by a thread of the parent while its main
    thread folds the reference: admits them as memory allows, polls what
    the tree of processes holds, reaps them."""

    def __init__(self, jobs, workers: int, child: int):
        super().__init__(daemon=True)
        self.ctx = multiprocessing.get_context("spawn")
        self.waiting = list(jobs)          # (k, job), in segment order
        self.running = {}                  # k -> (process, connection)
        self.seconds = {}                  # k -> the child's own seconds
        self.workers, self.child = workers, child
        self.free_start, self.limit = machine.free_memory()
        self.reserve = max(RESERVE_FLOOR_BYTES, self.limit // RESERVE_SHARE)
        self.free_min = self.free_start
        self.peak_tree = self.peak_child = self.peak_fold = 0
        self.held = set()                  # the children that stand still
        self.waited_s = 0.0
        self.error = None
        self._halt = threading.Event()
        # the first children start here, from the caller's thread and before
        # its fold takes the interpreter: all of them, where all fit
        self._poll(0.0)

    def _reap(self) -> None:
        for k, (proc, conn) in list(self.running.items()):
            if not conn.poll() and proc.is_alive():
                continue
            try:  # a pipe whose far end died is ready too, and empty
                what, value = conn.recv()
            except EOFError:
                what, value = "died", None
            proc.join()
            if what == "ok":
                self.seconds[k] = value
            elif what == "error":
                self.error = f"segment {k}'s child raised:\n{value}"
            else:
                self.error = (f"segment {k}'s child died with exit code "
                              f"{proc.exitcode} and said nothing")
            conn.close()
            del self.running[k]
            self.held.discard(k)

    def _stand(self, k: int, still: bool) -> None:
        os.kill(self.running[k][0].pid,
                signal.SIGSTOP if still else signal.SIGCONT)
        if still:
            self.held.add(k)
        else:
            self.held.discard(k)

    def _brake(self, free: int) -> None:
        """Less than the reserve is free: every child but the oldest stands
        still. The oldest living child never does, whatever is free: when it
        ends the next oldest goes on in its place, so a build on a machine
        that stays short goes one by one and ends. More than the reserve and
        a child's peak free: the oldest of those that stand goes on too."""
        alive = sorted(self.running)
        if free < self.reserve:
            for k in alive[1:]:
                if k not in self.held:
                    self._stand(k, True)
        if alive and alive[0] in self.held:
            self._stand(alive[0], False)
        elif self.held and free > self.reserve + self.child:
            self._stand(min(self.held), False)

    def _poll(self, dt: float) -> None:
        me = os.getpid()
        tree = machine.tree_rss(me)
        held_by = [tree.get(p.pid, 0) for p, _ in self.running.values()]
        self.peak_tree = max(self.peak_tree, sum(tree.values()))
        self.peak_child = max([self.peak_child] + held_by)
        self.peak_fold = max(self.peak_fold, tree.get(me, 0))
        free, _ = machine.free_memory()
        self.free_min = min(self.free_min, free)
        self._brake(free)
        n = 0 if self.held else admit(
            len(self.waiting), held_by, free, self.child, self.reserve,
            self.workers)
        if self.held or n < min(len(self.waiting),
                                self.workers - len(self.running)):
            self.waited_s += dt      # a core stood free and memory did not
        for k, job in self.waiting[:n]:
            recv, send = self.ctx.Pipe(duplex=False)
            proc = self.ctx.Process(target=_child_main,
                                    args=(send, me, job), daemon=True)
            proc.start()
            send.close()
            self.running[k] = (proc, recv)
        del self.waiting[:n]

    def run(self) -> None:
        last = time.time()
        try:
            while (self.waiting or self.running) and not self.error \
                    and not self._halt.is_set():
                self._reap()
                now = time.time()
                self._poll(now - last)
                last = now
                self._halt.wait(POLL_S)
        except Exception:  # noqa: BLE001 - the main thread raises it
            self.error = traceback.format_exc()

    def result(self, timeout: float) -> list:
        """Every child's seconds, in segment order, once all have ended."""
        self.join(timeout)
        if self.is_alive():
            self.error = f"not done {timeout:.0f} s after the fold's end"
        if self.error:
            raise RuntimeError("build_table: " + self.error)
        return [self.seconds[k] for k in sorted(self.seconds)]

    def stop(self) -> None:
        """Leave nothing running: every child that lives is killed."""
        self._halt.set()
        self.join()
        for proc, conn in self.running.values():
            proc.kill()
            proc.join()
            conn.close()
        self.running.clear()


def _build_segment_job(job):
    """Runs in a spawned child: draw segment ``k`` (the child is handed the
    seed, not 1 GB of columns), lay it out and build its files."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    config, seed, k, out_dir = job

    from pinot_tpu.common.schema import Schema
    from pinot_tpu.common.table_config import TableConfig
    from pinot_tpu.storage.creator import build_segment

    t0 = time.time()
    cols = _HandOver(lay_out(config, draw_segment(
        config, segment_rng(seed, k), seed=seed, k=k)))
    build_segment(Schema.from_json(config["schema"]), cols, out_dir,
                  TableConfig.from_json(config["table_config"]),
                  os.path.basename(out_dir))
    return time.time() - t0


def build_table(config: dict, seed: int, out_root: str, reference, say):
    """Build every segment in spawned children while this process draws the
    same columns from the same generator and folds those the reference
    needs into it. Returns the segment directories."""
    check_generator(config)
    n_seg = config["segments"]
    dirs = [os.path.join(out_root, f"s{k}") for k in range(n_seg)]
    workers = min(n_seg, max(1, (os.cpu_count() or 2) - 1))
    t0 = time.time()
    crew = _Crew([(k, (config, seed, k, out)) for k, out in enumerate(dirs)],
                 workers, child_peak_bytes(config))
    crew.start()
    try:
        for cols in reference_segments(config, seed, reference.columns):
            reference.add(cols)
        ref_s = time.time() - t0
        per_seg = crew.result(timeout=1100)
    finally:
        crew.stop()
    say(f"build segments={n_seg} rows_per_segment="
        f"{config['rows_per_segment']} layout={config['layout']['kind']} "
        f"seed={seed} workers={workers} seconds={time.time() - t0:.1f} "
        f"reference_seconds={ref_s:.1f} "
        f"slowest_segment_seconds={max(per_seg):.1f} "
        f"free_gb={crew.free_start / machine.GB:.2f} "
        f"child_gb={crew.child / machine.GB:.2f} "
        f"peak_tree_gb={crew.peak_tree / machine.GB:.2f} "
        f"limit_gb={crew.limit / machine.GB:.2f} "
        f"waited_s={crew.waited_s:.1f} "
        f"free_min_gb={crew.free_min / machine.GB:.2f} "
        f"child_max_gb={crew.peak_child / machine.GB:.2f} "
        f"fold_max_gb={crew.peak_fold / machine.GB:.2f}")
    return dirs
