"""What the harness reads off the machine it runs on: how much memory is
free now and how much there is, how much a run's tree of processes holds,
and which work directories in the temporary directory belong to runs that
are dead. Linux's ``/proc`` and nothing else: no option, no variable. Every
reader takes the files' place as an argument, so that a test hands it
sample files."""

from __future__ import annotations

import os
import shutil
import time

PAGE = os.sysconf("SC_PAGE_SIZE")
GB = 1e9
PID_FILE = "pid"
# a work directory that names no pid (a harness before PR 39, or a run
# between its mkdtemp and its claim) is a dead run's only once no run can
# still be alive: a cell's first run is allowed 1,200 s
UNCLAIMED_STALE_S = 1800.0


def _read(path: str):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def meminfo(path: str = "/proc/meminfo") -> dict:
    """``/proc/meminfo`` in bytes by key."""
    out = {}
    for line in (_read(path) or "").splitlines():
        key, _, rest = line.partition(":")
        if rest.split():
            out[key] = int(rest.split()[0]) * 1024
    return out


def free_memory(meminfo_path: str = "/proc/meminfo") -> tuple:
    """(bytes free now, the machine's memory): ``MemAvailable`` and
    ``MemTotal``. No cgroup is read: on the chip's machine none shows a
    limit (PERF.md s.7), and the keeper that ends a run there is a flag of
    the sandbox's init that no file shows."""
    info = meminfo(meminfo_path)
    return info.get("MemAvailable", info.get("MemFree", 0)), \
        info.get("MemTotal", 0)


def _stat(pid, proc: str = "/proc"):
    """(parent pid, start time in clock ticks since boot) of a process, or
    None where it is gone."""
    text = _read(os.path.join(proc, str(pid), "stat"))
    if not text:
        return None
    rest = text[text.rindex(")") + 2:].split()
    if rest[0] == "Z":  # dead, and waiting for its parent to ask
        return None
    return int(rest[1]), int(rest[19])


def rss_bytes(pid, proc: str = "/proc") -> int:
    text = _read(os.path.join(proc, str(pid), "statm"))
    return int(text.split()[1]) * PAGE if text else 0


def tree_rss(root_pid: int, proc: str = "/proc") -> dict:
    """pid -> resident bytes of ``root_pid`` and every descendant of it."""
    children: dict = {}
    for name in os.listdir(proc):
        if name.isdigit():
            st = _stat(name, proc)
            if st:
                children.setdefault(st[0], []).append(int(name))
    out, todo = {}, [root_pid]
    while todo:
        pid = todo.pop()
        out[pid] = rss_bytes(pid, proc)
        todo.extend(children.get(pid, ()))
    return out


def claim(work: str) -> None:
    """Say whose ``work`` is: this process's pid and start time, so that a
    later run can tell a dead run's directory from a live one's (a pid
    alone comes round again)."""
    with open(os.path.join(work, PID_FILE), "w") as f:
        f.write(f"{os.getpid()} {_stat(os.getpid())[1]}\n")


def _owner_lives(work: str, now: float, proc: str = "/proc") -> bool:
    said = (_read(os.path.join(work, PID_FILE)) or "").split()
    if len(said) == 2 and all(x.isdigit() for x in said):
        st = _stat(said[0], proc)
        return st is not None and st[1] == int(said[1])
    try:
        return now - os.stat(work).st_mtime < UNCLAIMED_STALE_S
    except OSError:
        return False


def sweep_stale(tmp: str, prefix: str, proc: str = "/proc") -> list:
    """Remove the work directories ``tmp/<prefix>*`` whose run is dead (a
    ``kill -9`` or the machine's limit never reaches a ``finally``), and
    never one whose process lives: parent and change may run side by side.
    Returns what it removed."""
    removed = []
    try:
        names = sorted(os.listdir(tmp))
    except OSError:
        return removed
    now = time.time()
    for name in names:
        work = os.path.join(tmp, name)
        if name.startswith(prefix) and os.path.isdir(work) \
                and not _owner_lives(work, now, proc):
            shutil.rmtree(work, ignore_errors=True)
            removed.append(work)
    return removed
