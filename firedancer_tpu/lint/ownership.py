"""Shm single-writer ownership + torn-read analysis.

Every shared-memory region in this tree has ONE writer class by
design — tango mcaches/fseqs have the producer, metric slots have
their owning tile (plus the supervisor's reserved `sup_*` slots),
trace rings belong to the tile they record, the funk root namespace
is written by a small cataloged set of lifecycle owners, the restore
marker by the snapshot inserter alone. Nothing enforces that: a new
module can import `SUP_SLOTS` and poke another tile's slots, or
rec_write the restore marker from the wrong side of the catch-up
gate, and the bug only shows up as a torn counter or a wedged
follower under chaos.

This analyzer makes region ownership a reviewed artifact:

  * dual-writer: each region class below carries the cataloged set of
    writer modules. A write-API call outside that set is a finding.
    Legitimate handoffs are annotated in place —
    `# fdlint: disable=dual-writer — handoff: <why>` — the
    supervisor's post-mortem append of reap marks into a dead tile's
    trace ring is the exemplar (the tile is provably dead, ownership
    transferred to the reaper).
  * torn-read: >=2 subscript reads of one live shm u64 view inside a
    function. The writer can land between the two loads, so the
    fields read belong to different states. Snapshot first with
    `tango.u64_snapshot(view)` (one copy, then coherent reads) — the
    metrics `seed_from` resurrect path had exactly this bug.

runtime/tango.py is exempt from torn-read: it IS the atomicity
discipline (speculative double-read of seq around the payload copy is
the tango protocol, not a bug).
"""
from __future__ import annotations

import ast
import re

from .core import Finding, filter_suppressed, finding

# receiver spelling filters keep generic method names (.event, .record)
# from matching unrelated objects
_TRACE_RECV = re.compile(r"(?:^|\.)_?(?:tr|trace)$")
_PROF_RECV = re.compile(r"(?:^|\.)_?(?:prof|region)$")

# region -> (doc, writer module suffixes). A suffix ending in "/"
# allows the whole subpackage.
SHM_REGIONS: dict[str, tuple[str, tuple[str, ...]]] = {
    "trace-ring": (
        "a tile's flight-recorder ring (trace/recorder.py); owned by "
        "the recording tile's process",
        ("trace/", "disco/stem.py", "disco/tiles.py", "tiles/",
         "prof/device.py", "disco/slo.py")),
    "sup-slots": (
        "the supervisor-reserved sup_* metric slots; owned by the "
        "supervisor loop alone — tiles only read them",
        ("disco/supervise.py",)),
    "restore-marker": (
        "the funk restore marker record; written once by the snapshot "
        "inserter when catch-up completes, read by replay's gate",
        ("tiles/snapshot.py",)),
    "funk-root": (
        "funk root-namespace records (rec_write(None, ...)); owned by "
        "the cataloged lifecycle writers (genesis, snapshot restore, "
        "checkpoint/vinyl load, bank/replay commit)",
        ("funk/", "utils/checkpt.py", "vinyl/vinyl.py",
         "tiles/snapshot.py", "tiles/replay.py", "disco/tiles.py",
         "app/genesis.py", "svm/accdb_cold.py",
         "flamenco/snapshot.py")),
    "prof-region": (
        "a tile's profiler region (ring + slot state + capture "
        "req/ack); written via ProfRegion APIs from the owning "
        "tile's sampler",
        ("prof/",)),
}

TORN_READ_EXEMPT = ("runtime/tango.py",)


def _rel(path: str) -> str:
    """Path relative to the package root, for writer-set matching."""
    p = path.replace("\\", "/")
    marker = "firedancer_tpu/"
    i = p.rfind(marker)
    return p[i + len(marker):] if i >= 0 else p


def _allowed(rel: str, writers: tuple[str, ...]) -> bool:
    for w in writers:
        if w.endswith("/"):
            if rel.startswith(w):
                return True
        elif rel == w or rel.endswith("/" + w):
            return True
    return False


def _recv_text(func: ast.Attribute) -> str:
    try:
        return ast.unparse(func.value)
    except Exception:               # pragma: no cover - defensive
        return ""


def _region_of_call(node: ast.Call) -> str | None:
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    name = f.attr
    if name in ("frag", "frag_batch", "event") and \
            _TRACE_RECV.search(_recv_text(f)):
        return "trace-ring"
    if name in ("record", "request_capture", "ack_capture") and \
            _PROF_RECV.search(_recv_text(f)):
        return "prof-region"
    if name in ("rec_write", "rec_remove"):
        for a in node.args:
            if "RESTORE_MARKER" in ast.unparse(a):
                return "restore-marker"
    if name == "rec_write" and node.args and \
            isinstance(node.args[0], ast.Constant) and \
            node.args[0].value is None:
        return "funk-root"
    return None


def _region_of_store(target: ast.AST) -> str | None:
    if isinstance(target, ast.Subscript) and \
            "SUP_SLOTS" in ast.unparse(target.slice):
        return "sup-slots"
    return None


def _check_dual_writer(tree: ast.Module, path: str) -> list[Finding]:
    rel = _rel(path)
    out: list[Finding] = []

    def emit(region: str, line: int):
        doc, writers = SHM_REGIONS[region]
        if _allowed(rel, writers):
            return
        out.append(finding(
            "dual-writer", path, line,
            f"write to single-writer shm region {region!r} ({doc}) "
            f"from {rel}, outside its cataloged writer set "
            f"{list(writers)} — if this is a deliberate ownership "
            f"handoff, annotate the line with "
            f"'# fdlint: disable=dual-writer — handoff: <why>'; "
            f"otherwise route the write through the owning tile"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            region = _region_of_call(node)
            if region:
                emit(region, node.lineno)
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                region = _region_of_store(t)
                if region:
                    emit(region, node.lineno)
    return out


# -- torn-read --------------------------------------------------------------

_VIEW_PARAM = re.compile(r"view")


def _live_views(fn: ast.AST) -> dict[str, int]:
    """name -> def line of locals/params holding a LIVE shm view (a
    `.view(...)` product that was not `.copy()`d)."""
    from .contracts import own_nodes
    out: dict[str, int] = {}
    args = getattr(fn, "args", None)
    if args is not None:
        for a in list(args.args) + list(args.kwonlyargs):
            if _VIEW_PARAM.search(a.arg):
                out[a.arg] = fn.lineno
    for n in own_nodes(fn):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name):
            try:
                text = ast.unparse(n.value)
            except Exception:       # pragma: no cover - defensive
                continue
            name = n.targets[0].id
            if ".view(" in text and ".copy(" not in text:
                out[name] = n.lineno
            elif name in out:
                out.pop(name)       # rebound to something harmless
    return out


def _check_torn_read(tree: ast.Module, path: str) -> list[Finding]:
    rel = _rel(path)
    if any(rel == e or rel.endswith("/" + e) for e in TORN_READ_EXEMPT):
        return []
    from .contracts import own_nodes
    out: list[Finding] = []
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        live = _live_views(fn)
        if not live:
            continue
        reads: dict[str, list[int]] = {}
        for n in own_nodes(fn):
            # scalar index loads only: slicing a view builds another
            # lazy view (no bytes move), it is not a torn value read
            if isinstance(n, ast.Subscript) and \
                    isinstance(n.ctx, ast.Load) and \
                    not isinstance(n.slice, ast.Slice) and \
                    isinstance(n.value, ast.Name) and \
                    n.value.id in live:
                reads.setdefault(n.value.id, []).append(n.lineno)
        for name, lines in sorted(reads.items()):
            if len(lines) >= 2:
                out.append(finding(
                    "torn-read", path, lines[1],
                    f"{fn.name}() reads live shm view {name!r} "
                    f"{len(lines)} times (lines {lines}) — the writer "
                    f"can land between the loads, so the fields belong "
                    f"to different states; snapshot once with "
                    f"tango.u64_snapshot({name}) and read the copy"))
    return out


def lint_ownership_source(source: str, path: str) -> list[Finding]:
    """Per-file ownership analysis: dual-writer + torn-read."""
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return []
    out = _check_dual_writer(tree, path)
    out.extend(_check_torn_read(tree, path))
    return filter_suppressed(out, source)
