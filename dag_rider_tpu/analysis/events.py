"""Event discipline: every event emitted must be registered.

``EventLog.event`` accepts any name — a typo'd event silently creates a
record that no trace report, flight-recorder trigger, or chrome export
row will ever join on (the causal chains in ``obs.report`` join on
EXACT event names; a misspelt ``tx_delivr`` just drops the transaction
from every latency percentile). The rule, mirroring the metrics
checker: any literal event name passed to ``*.event("...")`` must
appear in ``utils.slog.KNOWN_EVENTS``. Non-literal names (forwarding
loops) are out of scope.

Spans follow the same rule: a literal name given to ``obs.span`` /
``spans.record`` must be in ``obs.spans.KNOWN_SPANS`` and one given to
``obs.count`` in ``KNOWN_COUNTS`` — a misspelt span fills a row of the
book that no per-layer metric reads. And timing has one primitive:
``TraceAnnotation`` is named in ``obs/spans.py`` alone, so nothing puts
a stamp on the profiler's timeline that the book does not also count.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Sequence

from dag_rider_tpu.analysis.core import Finding, SourceFile
from dag_rider_tpu.obs.spans import KNOWN_COUNTS, KNOWN_SPANS
from dag_rider_tpu.utils.slog import KNOWN_EVENTS

CHECKER = "events"

_SPANS_FILE = "dag_rider_tpu/obs/spans.py"
#: the names the spans module is reached by at its call sites
_SPAN_RECEIVERS = ("obs", "spans")
#: method -> (its registry, the registry's name)
_SPAN_METHODS = {
    "span": (KNOWN_SPANS, "KNOWN_SPANS"),
    "record": (KNOWN_SPANS, "KNOWN_SPANS"),
    "count": (KNOWN_COUNTS, "KNOWN_COUNTS"),
}


def _literal(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _event_name(node: ast.AST) -> Optional[str]:
    """The literal event name this node emits, if any."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        if node.func.attr == "event" and node.args:
            return _literal(node.args[0])
    return None


def _span_problem(node: ast.AST) -> Optional[str]:
    """What is wrong with this node as a use of the span primitive."""
    if isinstance(node, ast.Attribute) and node.attr == "TraceAnnotation":
        return "TraceAnnotation outside obs/spans.py: open an obs.span"
    if not (isinstance(node, ast.Call) and node.args):
        return None
    func = node.func
    if not (isinstance(func, ast.Attribute) and func.attr in _SPAN_METHODS):
        return None
    recv = func.value
    recv_name = recv.attr if isinstance(recv, ast.Attribute) else (
        recv.id if isinstance(recv, ast.Name) else None
    )
    name = _literal(node.args[0])
    if recv_name not in _SPAN_RECEIVERS or name is None:
        return None
    known, registry = _SPAN_METHODS[func.attr]
    if name in known:
        return None
    return f"{func.attr} {name!r} is not registered in obs.spans.{registry}"


def run(files: Sequence[SourceFile], repo_root: str) -> List[Finding]:
    findings: List[Finding] = []
    for rel, tree, _src in files:
        if rel == "dag_rider_tpu/utils/slog.py":
            continue  # the registry itself
        for node in ast.walk(tree):
            problem = None if rel == _SPANS_FILE else _span_problem(node)
            if problem is not None:
                findings.append(Finding(CHECKER, rel, node.lineno, problem))
            name = _event_name(node)
            if name is not None and name not in KNOWN_EVENTS:
                findings.append(
                    Finding(
                        CHECKER,
                        rel,
                        node.lineno,
                        f"event {name!r} is not registered in "
                        "utils.slog.KNOWN_EVENTS",
                    )
                )
    return findings
