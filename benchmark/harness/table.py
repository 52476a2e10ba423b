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
"""

from __future__ import annotations

import multiprocessing
import os
import time

import numpy as np

from . import spec as spec_mod


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
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(workers) as pool:
        pending = [pool.apply_async(_build_segment_job,
                                    ((config, seed, k, out),))
                   for k, out in enumerate(dirs)]
        for cols in reference_segments(config, seed, reference.columns):
            reference.add(cols)
        ref_s = time.time() - t0
        per_seg = [p.get(timeout=1100) for p in pending]
    say(f"build segments={n_seg} rows_per_segment="
        f"{config['rows_per_segment']} layout={config['layout']['kind']} "
        f"seed={seed} workers={workers} seconds={time.time() - t0:.1f} "
        f"reference_seconds={ref_s:.1f} "
        f"slowest_segment_seconds={max(per_seg):.1f}")
    return dirs
