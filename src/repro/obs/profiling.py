"""Stage-level profiling for the federated round pipeline.

Device side.  The round is a pipeline of named stages (repro.core.engine):
FedSAE's own prediction and selection, then gather -> local SGD -> upload
transform -> aggregate.  ``stage(name)`` is a ``jax.named_scope``: the
stage name joins the ``op_name`` metadata of every HLO op traced inside,
and survives into the compiled program, fusions included (a fusion takes
its root's ``op_name``).  ``stage_map(hlo_text)`` reads that metadata back
from a compiled program and maps every instruction to its innermost
``fed.*`` stage, so the ``XLA Ops`` events of a device trace, which are
named by instruction, can be attributed to stages
(``FedSAEServer.segment_stage_map``).  A Pallas kernel's custom call is
named after the innermost scope it is called in (``%fed.local_sgd.N``).
Scopes add metadata, never ops: scoped programs are bitwise the unscoped
ones (tests/test_telemetry.py).

Host side.  ``host_span(name, log, block)`` times one phase of the scan
driver's host loop (``fed.host.*``): a ``jax.profiler.TraceAnnotation``, so
the span lands on the device trace's clock when a profiler is on, and a
``(name, block, t0, t1)`` entry of ``time.perf_counter`` stamps appended to
a bounded log, so the phases can be read with no profiler at all.

Capture a trace with ``trace_if(dir)`` (fl_train's ``--trace-dir``).
"""
from __future__ import annotations

import contextlib
import re
import time
from typing import Dict, Iterator, Optional, Tuple

import jax

# canonical stage names: the scopes of the device program
STAGE_PREDICT = "fed.predict"
STAGE_SELECT = "fed.select"
STAGE_GATHER = "fed.gather"
STAGE_LOCAL_SGD = "fed.local_sgd"
STAGE_UPLOAD = "fed.upload_transform"
STAGE_AGGREGATE = "fed.aggregate"

# the scan driver's host phases, in the order a block runs them
HOST_DISPATCH = "fed.host.dispatch"
HOST_PULL = "fed.host.pull"
HOST_EVAL = "fed.host.eval"
HOST_RECORDS = "fed.host.records"
HOST_CHECKPOINT = "fed.host.checkpoint"
HOST_PHASES = (HOST_DISPATCH, HOST_PULL, HOST_EVAL, HOST_RECORDS,
               HOST_CHECKPOINT)
# the step annotation around each block of the scan driver
HOST_BLOCK = "fed.block"

# a ``fed.*`` component of an op_name path, e.g. ``fed.aggregate`` in
# ``jit(segment)/while/body/fed.aggregate/mul``
_STAGE_RX = re.compile(r"(?<![\w.])fed\.[A-Za-z_]+(?![\w.])")
_MODULE_RX = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTRUCTION_RX = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s=\s")
_OP_NAME_RX = re.compile(r'op_name="([^"]*)"')


@contextlib.contextmanager
def stage(name: str) -> Iterator[None]:
    """Named device scope for one pipeline stage."""
    with jax.named_scope(name):
        yield


def stage_map(hlo_text: str) -> Tuple[str, Dict[str, Optional[str]]]:
    """``(module name, {instruction name: stage or None})`` of a compiled
    program's HLO text (``Compiled.as_text()``): each instruction's stage
    is the innermost ``fed.*`` component of its ``op_name``, None where it
    has none."""
    module = ""
    stages: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = _MODULE_RX.match(line)
        if m:
            module = m.group(1)
            continue
        m = _INSTRUCTION_RX.match(line)
        if m:
            op = _OP_NAME_RX.search(line)
            hits = _STAGE_RX.findall(op.group(1)) if op else []
            stages[m.group(1)] = hits[-1] if hits else None
    return module, stages


@contextlib.contextmanager
def host_span(name: str, log, block: int) -> Iterator[None]:
    """One host phase of block ``block``: a profiler TraceMe named
    ``name``, and ``(name, block, t0, t1)`` appended to ``log``."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        yield
        log.append((name, block, t0, time.perf_counter()))


@contextlib.contextmanager
def trace_if(trace_dir: Optional[str]) -> Iterator[None]:
    """Capture a profiler trace into ``trace_dir`` when it is set; no-op
    otherwise — callers wrap their run unconditionally."""
    if not trace_dir:
        yield
        return
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
