"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Layout on a TPU v5e with JAX 0.9.0, looked at by hand on a trace of the
``mnist-mclr.fassa-k30`` cell (``testdata/``): each chip is a plane
``/device:TPU:<n>``.  Its line ``XLA Modules`` has one event per executed
program (``jit_segment(<fingerprint>)``, ``jit_eval_fn(...)``), its line
``XLA Ops`` one event per executed HLO instruction, named by the
instruction's text (``%fusion.190 = s32[30,960,10]{...} fusion(...)``).
The events carry only ``device_offset_ps`` and ``device_duration_ps``:
no stat holds the op's name scope, so the ``fed.*`` stage scopes of
``repro.obs.profiling`` cannot be read from this line.  A Pallas kernel's
custom call is named after the scope it was called in: ``%fed.gather.N``
is the ``fed_gather`` kernel, ``%fed.local_sgd.N`` the ``fed_local_sgd``
kernel, ``%fed.upload_transform.N`` the ``fed_compress`` kernel.  The
scan's ``%while.N`` op spans its body's ops, which are events of their
own.  Host threads are the lines of ``/host:CPU``.  All planes share one
clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

_DEVICE = re.compile(r"^/device:TPU:\d+$")
# ops that only contain other ops' events: the scan's while loop and the
# like; their time is their body's
_CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_name(hlo_text: str) -> str:
    """``%fusion.190 = s32[...] fusion(...)`` -> ``fusion.190``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


class Reduction:
    """The traced window, reduced.  ``ops`` are (HLO text, start, end) of
    every traced chip's operations, ``host`` the host events as (name,
    start, end); times in ns."""

    def __init__(self, ops: List[Tuple[str, int, int]],
                 host: List[Tuple[str, int, int]], n_devices: int,
                 window_s: float):
        self.ops = ops
        self.host = host
        self.n_devices = max(1, n_devices)
        self.window_s = float(window_s)
        self.busy = _union([(s, e) for _, s, e in ops])
        # busy seconds, averaged over the chips traced
        self.busy_s = sum(e - s for s, e in self.busy) * 1e-9 \
            / self.n_devices

    def kernel(self, name: str) -> Tuple[float, int]:
        """(device seconds, calls) of the custom calls named ``name.N``."""
        rx = re.compile(rf"^%{re.escape(name)}\.\d+ = .*custom-call\(")
        hits = [(s, e) for t, s, e in self.ops if rx.match(t)]
        return sum(e - s for s, e in hits) * 1e-9, len(hits)

    def gaps(self) -> List[Tuple[int, int]]:
        return [(a[1], b[0]) for a, b in zip(self.busy, self.busy[1:])
                if b[0] > a[1]]

    def host_activity(self, start: int, end: int) -> str:
        """The innermost host event that covers at least half of
        ``[start, end)``, else the one that covers most of it."""
        best, key = "no host event", None
        for name, s, e in self.host:
            cover = min(e, end) - max(s, start)
            if cover <= 0:
                continue
            half = 2 * cover >= end - start
            k = (half, -(e - s) if half else cover)
            if key is None or k > key:
                best, key = name, k
        return best

    def breakdown(self) -> Dict[str, List]:
        """The ten ops with most device time (containers left out) and
        the ten longest idle gaps, each named by the host's activity."""
        by_op: Dict[str, float] = {}
        for text, s, e in self.ops:
            if _CONTAINER.search(text):
                continue
            name = short_name(text)
            by_op[name] = by_op.get(name, 0.0) + (e - s) * 1e-9
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[self.host_activity(s, e), (e - s) * 1e-9]
                              for s, e in gaps]}


def from_profile(pd, window_s: float) -> Reduction:
    ops: List[Tuple[str, int, int]] = []
    host: List[Tuple[str, int, int]] = []
    n_devices = 0
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            n_devices += 1
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    ops.append((e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    host.append((e.name, s, s + int(e.duration_ns)))
    return Reduction(ops, host, n_devices, window_s)


def reduce(prof_dir: str, window_s: float) -> Reduction:
    """Reduce the one trace under ``prof_dir``."""
    import jax
    files = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {prof_dir}, found "
                           f"{len(files)}")
    return from_profile(jax.profiler.ProfileData.from_file(files[0]),
                        window_s)
