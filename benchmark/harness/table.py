"""The table of a configuration: seeded column generator, segment layout,
and the spawned children that turn each segment's columns into segment
files with the program's ``build_segment``.

The generator is a copy of ``pinot_tpu/tools/ssb.py segment_columns`` turned
into data: a configuration's ``generator`` lists one draw per column, and the
draws are made in that order. Segment ``k`` has a generator of its own,
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


def _date_value(spec: dict, day_index):
    """Day index in [0, years*months*days) -> the date-like int."""
    per_year = spec["months"] * spec["days"]
    y, rest = np.divmod(day_index, per_year)
    m, d = np.divmod(rest, spec["days"])
    return spec["base"] + y * 10000 + m * 100 + d


def domain_size(spec: dict) -> int:
    """How many distinct values a generated column can take."""
    kind = spec["kind"]
    if kind == "integers":
        return spec["high"] - spec["low"]
    if kind == "choice":
        return len(spec["values"])
    if kind == "date_ymd":
        return spec["years"] * spec["months"] * spec["days"]
    raise ValueError(f"unknown generator kind {kind!r}")


def column_spec(config: dict, column: str) -> dict:
    for spec in config["generator"]:
        if spec["column"] == column:
            return spec
    raise KeyError(f"configuration {config['name']} generates no {column!r}")


def segment_rng(seed: int, k: int) -> np.random.Generator:
    return np.random.default_rng([seed, k])


def draw_segment(config: dict, rng: np.random.Generator, only=None) -> dict:
    """One segment's columns in generated order. Every draw is made, so
    that a column's values do not depend on which others are asked for;
    ``only`` names the columns worth materialising."""
    n = config["rows_per_segment"]
    out = {}
    for spec in config["generator"]:
        name, kind = spec["column"], spec["kind"]
        keep = only is None or name in only
        if kind == "integers":
            v = rng.integers(spec["low"], spec["high"], n)
            if keep:
                out[name] = v.astype(np.int32)
        elif kind == "choice":
            v = rng.integers(0, len(spec["values"]), n)
            if keep:
                out[name] = np.array(spec["values"])[v]
        elif kind == "date_ymd":
            y = rng.integers(0, spec["years"], n)
            m = rng.integers(0, spec["months"], n)
            d = rng.integers(0, spec["days"], n)
            if keep:
                out[name] = (spec["base"] + y * 10000 + m * 100 + d
                             ).astype(np.int32)
        else:
            raise ValueError(f"unknown generator kind {kind!r}")
    return out


def segment_date_range(config: dict, k: int):
    """(lowest, highest) value of the layout column in segment ``k``, or
    None where the layout leaves every segment spanning every value."""
    layout = config["layout"]
    if layout["kind"] == "generated":
        return None
    spec = column_spec(config, layout["column"])
    per_seg, rem = divmod(domain_size(spec), config["segments"])
    if rem:
        raise ValueError("by_date needs the date values to divide by the "
                         "segment count")
    return (int(_date_value(spec, k * per_seg)),
            int(_date_value(spec, (k + 1) * per_seg - 1)))


def remap_layout_column(config: dict, k: int, cols: dict) -> None:
    """by_date: segment ``k`` of S keeps its rows and re-maps the layout
    column to day index ``per_seg*k + day_index // S`` (in place). The
    answer to a statement does not depend on row order, so this is all the
    reference needs."""
    layout = config["layout"]
    if layout["kind"] == "generated":
        return
    if layout["kind"] != "by_date":
        raise ValueError(f"unknown layout {layout['kind']!r}")
    spec = column_spec(config, layout["column"])
    s = config["segments"]
    per_seg = domain_size(spec) // s
    segment_date_range(config, k)  # raises where it does not divide
    # a table from old value to new: the column has few distinct values
    days = np.arange(domain_size(spec))
    old = _date_value(spec, days) - spec["base"]
    lut = np.zeros(old.max() + 1, dtype=np.int32)
    lut[old] = _date_value(spec, per_seg * k + days // s)
    cols[layout["column"]] = lut[cols[layout["column"]] - spec["base"]]


def reference_segments(config: dict, seed: int, only):
    """Each segment's columns as the reference needs them: drawn from the
    segment's own generator, the layout column re-mapped, rows unsorted."""
    for k in range(config["segments"]):
        cols = draw_segment(config, segment_rng(seed, k), only=only)
        remap_layout_column(config, k, cols)
        yield cols


def lay_out(config: dict, k: int, cols: dict) -> dict:
    """The segment as it is stored: by_date re-maps the layout column and
    stable-sorts the rows by it."""
    if config["layout"]["kind"] == "generated":
        return cols
    remap_layout_column(config, k, cols)
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
    cols = _HandOver(
        lay_out(config, k, draw_segment(config, segment_rng(seed, k))))
    build_segment(Schema.from_json(config["schema"]), cols, out_dir,
                  TableConfig.from_json(config["table_config"]),
                  os.path.basename(out_dir))
    return time.time() - t0


def build_table(config: dict, seed: int, out_root: str, reference, say):
    """Build every segment in spawned children while this process draws the
    same columns from the same generator and folds those the reference
    needs into it. Returns the segment directories."""
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
