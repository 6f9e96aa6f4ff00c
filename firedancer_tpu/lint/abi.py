"""Wire/shm ABI contract analysis: the cross-process byte contracts.

Firedancer's concurrency model is processes agreeing on hand-rolled
binary contracts — ring frames, wksp offsets, metric slots — with no
compiler checking either side. This analyzer makes the two ABI bug
classes that already bit this clone statically impossible to ship:

  * wire-mismatch: every cataloged `Ring.publish`/consume site's
    struct format strings are AST-extracted and pinned against the
    WIRE_CONTRACTS catalog below. Editing one side of a wire (or the
    catalog) without the other is a review-time finding at the site
    that drifted. Formats are compared whitespace-normalized (struct
    ignores whitespace) and resolve module-level
    `_X = struct.Struct(fmt)` constants.
  * short-key: any bytes key reaching a store/funk API must provably
    be 32 bytes wide — the exact r17 `_key32` class (the native store
    ABI reads EXACTLY 32 key bytes; a 15-byte python buffer hashed
    per-process trailing garbage and wedged the follower gate).
    Accepted proofs: a 32-byte literal/slice/concatenation, a
    `*key32*(...)` call, `.digest()`, `bytes(32)`, `.ljust(32, ...)`,
    an ALL_CAPS module constant (reviewed at its definition), or —
    for a plain name — a same-scope `assert len(k) == 32` /
    `if len(k) != 32: raise` guard or `key32(k)` call.
  * registry-drift: lint/registry.py's hand-maintained mirrors are
    recomputed from the code they mirror — adapter `args.get(...)`
    keys vs TILE_ARGS, and each `[section]` key tuple vs the owning
    module's *_DEFAULTS dict.

The wire-mtu rule (frame size vs link mtu for the exec/replay/shred
wire families) lives in lint/graph.py's `_check_wire_mtus`, because
attributing a wire to its link needs the topology model; the minimums
it enforces are mirrored in registry.py next to the older growth
contracts.
"""
from __future__ import annotations

import ast
import os
import re

from .core import Finding, filter_suppressed, finding
from . import registry as reg

# ---------------------------------------------------------------------------
# struct-format extraction
# ---------------------------------------------------------------------------

_STRUCT_FNS = ("pack", "pack_into", "unpack", "unpack_from",
               "iter_unpack", "calcsize")


def _norm_fmt(fmt: str) -> str:
    return re.sub(r"\s+", "", fmt)


def _struct_consts(tree: ast.Module) -> dict[str, str]:
    """module-level `_X = struct.Struct("fmt")` name -> fmt."""
    out: dict[str, str] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name):
            v = n.value
            if isinstance(v, ast.Call) and \
                    isinstance(v.func, ast.Attribute) and \
                    v.func.attr == "Struct" and v.args and \
                    isinstance(v.args[0], ast.Constant) and \
                    isinstance(v.args[0].value, str):
                out[n.targets[0].id] = v.args[0].value
    return out


def _formats_in(node: ast.AST, consts: dict[str, str]) -> dict[str, int]:
    """normalized format -> first line, for every struct call under
    `node` (struct.pack/unpack*, struct.Struct, and pack/unpack on a
    module-level Struct constant)."""
    out: dict[str, int] = {}

    def add(fmt: str, line: int):
        fmt = _norm_fmt(fmt)
        if fmt not in out:
            out[fmt] = line
    for n in ast.walk(node):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if not isinstance(f, ast.Attribute):
            continue
        recv = f.value
        if isinstance(recv, ast.Name) and recv.id == "struct" and \
                f.attr in _STRUCT_FNS + ("Struct",):
            if n.args and isinstance(n.args[0], ast.Constant) and \
                    isinstance(n.args[0].value, str):
                add(n.args[0].value, n.lineno)
        elif f.attr in _STRUCT_FNS:
            name = recv.id if isinstance(recv, ast.Name) else (
                recv.attr if isinstance(recv, ast.Attribute) else None)
            if name in consts:
                add(consts[name], n.lineno)
    return out


def module_format_map(source: str) -> dict[str, dict[str, int]]:
    """qualname ("Class.method" or "function") -> {fmt: first line}.
    Nested defs fold into their enclosing top-level def (the wire site
    granularity the catalog pins)."""
    tree = ast.parse(source)
    consts = _struct_consts(tree)
    out: dict[str, dict[str, int]] = {}
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            for st in node.body:
                if isinstance(st, ast.FunctionDef):
                    fmts = _formats_in(st, consts)
                    if fmts:
                        out[f"{node.name}.{st.name}"] = fmts
        elif isinstance(node, ast.FunctionDef):
            fmts = _formats_in(node, consts)
            if fmts:
                out[node.name] = fmts
    return out


# ---------------------------------------------------------------------------
# the wire-contract catalog
# ---------------------------------------------------------------------------

# Each contract: (wire name, layout doc, sites); each site:
# (module path suffix, qualname, expected normalized formats). A site
# whose extracted formats lose one of these — or that vanishes — is a
# wire-mismatch finding; extra formats at a cataloged site (uncataloged
# ABI growth) are flagged by the exact-cover pass below. Sites list
# BOTH directions of every wire, so editing a producer's pack without
# its consumers' unpack (or vice versa) cannot pass review silently.
WIRE_CONTRACTS: tuple = (
    ("pack->bank microblock",
     "<HHQQ> 20B header (bank, txn_cnt, mb_id, slot) + <H>-framed "
     "txn payloads",
     (("disco/tiles.py", "PackAdapter._serialize", ("<H", "<HHQQ")),
      ("disco/tiles.py", "BankAdapter.poll_once", ("<HHQQ",)),
      ("disco/tiles.py", "BankAdapter._poll_exec_family", ("<HHQQ",)),
      ("disco/tiles.py", "BankAdapter._parse_payloads", ("<H",)),
      ("disco/tiles.py", "BankAdapter._parse_transfers", ("<H",)))),
    ("bank->pack done",
     "<QH> (mb_id, txn_cnt) per retired microblock; <Q> slot flush",
     (("disco/tiles.py", "BankAdapter._finalize_wave", ("<QH",)),
      ("disco/tiles.py", "BankAdapter._ef_commit", ("<QH",)),
      ("disco/tiles.py", "BankAdapter._wave_general", ("<QH",)),
      ("disco/tiles.py", "BankAdapter._flush_wave", ("<Q",)),
      ("disco/tiles.py", "PackAdapter.poll_once", ("<H", "<Q")))),
    ("bank/replay->exec dispatch + exec->done (r16 wire)",
     "<QQH> (wave_seq, xid, txn_cnt) + 80B rows (32B src + 32B dst + "
     "<QQ> amount,fee); completion <QII> (wave_seq, ok, fail)",
     (("disco/tiles.py", "ExecFanout._send", ("<QQ", "<QQH")),
      ("disco/tiles.py", "ExecFanout.poll", ("<QII",)),
      ("disco/tiles.py", "ExecAdapter.poll_once",
       ("<QQ", "<QQH", "<QII")))),
    ("bank->poh microblock handoff",
     "42B header; poh reads the txn_cnt <H> at offset 8",
     (("disco/tiles.py", "PohAdapter.poll_once", ("<H",)),)),
    ("poh->shred entry wire",
     "<QIIB> (slot, tick, num_hashes, has_mix) + 32B hash + <H> txn "
     "blob; shred re-frames into <I>-counted entry batches",
     (("disco/tiles.py", "PohAdapter._emit_entry", ("<H", "<QIIB")),
      ("tiles/shred.py", "ShredLeaderCore.on_entry",
       ("<H", "<I", "<QIIB")),
      ("tiles/shred.py", "parse_entry_batch", ("<H", "<I")))),
    ("poh slot wire",
     "<Q> completed slot",
     (("disco/tiles.py", "PohAdapter._flush_pending", ("<Q",)),)),
    ("shred->replay slice wire (r17)",
     "<QIB> (slot, first_fec_idx, done) + entry-batch payload",
     (("tiles/shred.py", "pack_slice", ("<QIB",)),
      ("tiles/shred.py", "parse_slice", ("<QIB",)))),
    ("shred wire (turbine/repair)",
     "fixed header: slot <Q> at 0x41, idx <I> at 0x49; batch flush "
     "<QB>",
     (("tiles/shred.py", "ShredLeaderCore._flush", ("<QB",)),
      ("tiles/shred.py", "ShredLeaderCore._tx", ("<I",)),
      ("tiles/shred.py", "ShredRecoverCore.on_shred", ("<I", "<Q")),
      ("tiles/shred.py", "ShredRecoverCore._retransmit",
       ("<I", "<Q")))),
    ("replay->tower block/vote wire",
     "block: tag + <QQ> (slot, parent) + 2x32B ids; vote: tag + 32B "
     "voter + <Q> stake + 32B block id",
     (("tiles/tower.py", "pack_block", ("<QQ",)),
      ("tiles/tower.py", "pack_vote", ("<Q",)),
      ("tiles/tower.py", "TowerCore.handle", ("<Q", "<QQ")))),
    ("tower->send root/votes wire",
     "<Q> slot + 32B block id + optional root <Q> + <H>-counted "
     "<QI> (slot, conf) votes",
     (("disco/tiles.py", "TowerAdapter.housekeeping",
       ("<H", "<Q", "<QI")),
      ("disco/tiles.py", "SendAdapter.poll_once", ("<H", "<Q", "<QI")))),
    ("archiver record wire",
     "<QQHI> (seq, sig, ctl, sz) + payload, one record per frag",
     (("tiles/archiver.py", "ArchiveWriter.poll_once", ("<QQHI",)),
      ("tiles/archiver.py", "ArchivePlayback.poll_once", ("<QQHI",)))),
    ("vinyl req/resp wire",
     "req: op u8 + <Q> req_id + 32B key [+ value]; resp: <QB> "
     "(req_id, status) [+ value]",
     (("disco/tiles.py", "VinylAdapter._serve", ("<Q", "<QB")),)),
    ("funk account codec (snapshot/checkpt/vinyl shared)",
     "<Q32sBQ> account header + tag-framed <Q> value frames",
     (("funk/shmfunk.py", "encode_value", ("<Q", "<Q32sBQ")),
      ("funk/shmfunk.py", "decode_value", ("<Q", "<Q32sBQ")))),
)


def pkg_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _contract_files() -> dict[str, str]:
    """module path suffix -> absolute path for every cataloged module."""
    root = pkg_root()
    out = {}
    for _, _, sites in WIRE_CONTRACTS:
        for suffix, _, _ in sites:
            out[suffix] = os.path.join(root, *suffix.split("/"))
    return out


def lint_wire_contracts(
        sources: dict[str, str] | None = None) -> list[Finding]:
    """Check every WIRE_CONTRACTS site. `sources` (path suffix ->
    module source) overrides the shipped tree — fixtures inject a
    skewed module to prove the analyzer catches a seeded mismatch."""
    if sources is None:
        sources = {}
        for suffix, path in _contract_files().items():
            try:
                with open(path) as f:
                    sources[suffix] = f.read()
            except OSError:
                sources[suffix] = ""
    maps: dict[str, dict[str, dict[str, int]]] = {}
    for suffix, src in sources.items():
        try:
            maps[suffix] = module_format_map(src)
        except SyntaxError:
            maps[suffix] = {}
    out: list[Finding] = []
    cataloged: dict[tuple[str, str], set[str]] = {}
    for wire, _doc, sites in WIRE_CONTRACTS:
        for suffix, qual, fmts in sites:
            if suffix not in sources:
                continue                # fixture runs scope to one file
            want = {_norm_fmt(f) for f in fmts}
            cataloged.setdefault((suffix, qual), set()).update(want)
            got = maps[suffix].get(qual)
            if got is None:
                out.append(finding(
                    "wire-mismatch", suffix, 0,
                    f"wire {wire!r}: cataloged site {qual}() vanished "
                    f"(renamed or dropped) — re-sync lint/abi.py "
                    f"WIRE_CONTRACTS with both sides of the wire"))
                continue
            missing = want - set(got)
            if missing:
                line = min(got.values()) if got else 0
                out.append(finding(
                    "wire-mismatch", suffix, line,
                    f"wire {wire!r}: {qual}() no longer uses "
                    f"{sorted(missing)} (found {sorted(got)}) — the "
                    f"other side of this wire still parses the "
                    f"cataloged layout"))
    # exact cover: a cataloged site growing a NEW format is silent ABI
    # drift until its counterpart sites and the catalog acknowledge it
    for (suffix, qual), want in sorted(cataloged.items()):
        got = maps.get(suffix, {}).get(qual)
        if not got:
            continue
        extra = set(got) - want
        for fmt in sorted(extra):
            out.append(finding(
                "wire-mismatch", suffix, got[fmt],
                f"{qual}() uses format {fmt!r} not in its "
                f"WIRE_CONTRACTS entry — if the wire grew, update the "
                f"catalog AND every counterpart site"))
    filtered: list[Finding] = []
    for f in out:
        src = sources.get(f.path, "")
        filtered.extend(filter_suppressed([f], src))
    return filtered


# ---------------------------------------------------------------------------
# short-key: fixed-width keys from unvalidated-length sources
# ---------------------------------------------------------------------------

# method name -> positional index of the key argument. WRITE apis
# only: a short-key write poisons shared state permanently (the record
# lands under a garbage-extended key no other process can derive); a
# short-key read just misses, loudly and locally.
_KEY_APIS = {"rec_write": 1, "rec_remove": 1}
_KV_APIS = {"put": 0, "delete": 0}
_KV_RECEIVER = re.compile(r"(?:^|\.)(?:db|store|funk|vinyl)$")

KEY_WIDTH = 32


def _const_len(node: ast.AST) -> int | None:
    """Provable byte width of an expression, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, bytes):
        return len(node.value)
    if isinstance(node, ast.Subscript) and \
            isinstance(node.slice, ast.Slice) and node.slice.step is None:
        lo, hi = node.slice.lower, node.slice.upper
        lo_v = 0 if lo is None else (
            lo.value if isinstance(lo, ast.Constant) and
            isinstance(lo.value, int) else None)
        hi_v = hi.value if isinstance(hi, ast.Constant) and \
            isinstance(hi.value, int) else None
        if lo_v is not None and hi_v is not None and 0 <= lo_v <= hi_v:
            return hi_v - lo_v
        return None
    if isinstance(node, ast.Call):
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if "key32" in name or name == "digest":
            return KEY_WIDTH
        if name in ("ljust", "rjust", "to_bytes", "bytes") and \
                node.args and isinstance(node.args[0], ast.Constant) and \
                isinstance(node.args[0].value, int):
            return node.args[0].value
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left, right = _const_len(node.left), _const_len(node.right)
        if left is not None and right is not None:
            return left + right
    return None


def _len_checked_exprs(fn: ast.AST) -> set[str]:
    """Unparsed expressions `x` with a same-scope `len(x) == 32` /
    `len(x) != 32` guard (assert or if-raise) or a `*key32*(x)` call —
    the width-normalization proofs the short-key rule accepts."""
    from .contracts import own_nodes
    out: set[str] = set()
    for n in own_nodes(fn):
        if isinstance(n, ast.Compare) and len(n.comparators) == 1 and \
                isinstance(n.ops[0], (ast.Eq, ast.NotEq)):
            for side in (n.left, n.comparators[0]):
                if isinstance(side, ast.Call) and \
                        isinstance(side.func, ast.Name) and \
                        side.func.id == "len" and side.args:
                    other = n.comparators[0] if side is n.left else n.left
                    if isinstance(other, ast.Constant) and \
                            other.value == KEY_WIDTH:
                        out.add(ast.unparse(side.args[0]))
        elif isinstance(n, ast.Call):
            f = n.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else "")
            if "key32" in name:
                for a in n.args:
                    out.add(ast.unparse(a))
        elif isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                _const_len(n.value) == KEY_WIDTH:
            out.add(n.targets[0].id)
    return out


def _key_arg(node: ast.Call) -> tuple[ast.AST, str] | None:
    """(key expression, api name) when `node` calls a store/funk key
    API, else None."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    name = f.attr
    if name in _KEY_APIS:
        idx = _KEY_APIS[name]
    elif name in _KV_APIS and \
            _KV_RECEIVER.search(ast.unparse(f.value)):
        idx = _KV_APIS[name]
    else:
        return None
    if len(node.args) <= idx:
        return None
    return node.args[idx], name


def lint_abi_source(source: str, path: str) -> list[Finding]:
    """Per-file short-key analysis (the wire/registry passes are
    tree-level; see lint_wire_contracts / lint_registry_drift)."""
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return []
    out: list[Finding] = []
    fns = [n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    from .contracts import own_nodes
    for fn in fns:
        checked: set[str] | None = None     # computed lazily per fn
        for node in own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            hit = _key_arg(node)
            if hit is None:
                continue
            key, api = hit
            width = _const_len(key)
            if width == KEY_WIDTH:
                continue
            if width is not None:
                out.append(finding(
                    "short-key", path, node.lineno,
                    f"{api}() key is provably {width} bytes, store "
                    f"keys are {KEY_WIDTH} — the native ABI reads "
                    f"exactly {KEY_WIDTH} and hashes per-process "
                    f"trailing garbage past a short buffer"))
                continue
            if isinstance(key, ast.Name) and key.id.isupper():
                continue        # module constant, reviewed at its def
            if checked is None:
                checked = _len_checked_exprs(fn)
            if ast.unparse(key) in checked:
                continue
            out.append(finding(
                "short-key", path, node.lineno,
                f"{api}() key {ast.unparse(key)!r} has no provable "
                f"{KEY_WIDTH}-byte width in {fn.name}() — pass it "
                f"through a width-normalizing helper (key32 / "
                f".digest() / .ljust({KEY_WIDTH},...)) or guard with "
                f"len(...) == {KEY_WIDTH}"))
    return filter_suppressed(out, source)


# ---------------------------------------------------------------------------
# registry drift: the analyzer computes the mirror
# ---------------------------------------------------------------------------

# section -> (owning module suffix, defaults symbol, registry tuple
# name, keys in the registry tuple that are structural sub-tables or
# reference lists resolved by the graph analyzer, not defaults)
SECTION_MIRRORS = (
    ("trace", "trace/recorder.py", "TRACE_DEFAULTS",
     "TRACE_SECTION_KEYS", ()),
    ("prof", "prof/recorder.py", "PROF_DEFAULTS",
     "PROF_SECTION_KEYS", ()),
    ("slo", "disco/slo.py", "SLO_DEFAULTS", "SLO_SECTION_KEYS", ()),
    ("shed", "disco/shed.py", "SHED_DEFAULTS", "SHED_SECTION_KEYS", ()),
    ("funk", "funk/shmfunk.py", "FUNK_DEFAULTS",
     "FUNK_SECTION_KEYS", ()),
    ("replay", "tiles/replay.py", "REPLAY_DEFAULTS",
     "REPLAY_SECTION_KEYS", ()),
    ("snapshot", "tiles/snapshot.py", "SNAPSHOT_DEFAULTS",
     "SNAPSHOT_SECTION_KEYS", ()),
    ("witness", "witness/plan.py", "WITNESS_DEFAULTS",
     "WITNESS_SECTION_KEYS", ("stage",)),
    ("flight", "flight/__init__.py", "FLIGHT_DEFAULTS",
     "FLIGHT_SECTION_KEYS", ()),
)

_ADAPTERS_SUFFIX = "disco/tiles.py"


def _dict_literal_keys(source: str, symbol: str) -> set[str] | None:
    """Keys of a module-level `SYMBOL = {...}` dict literal, extracted
    statically (no import: the owning modules pull in jax/numpy)."""
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return None
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == symbol and \
                isinstance(node.value, ast.Dict):
            keys = set()
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    keys.add(k.value)
            return keys
    return None


def _registry_line(symbol: str) -> tuple[str, int]:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "registry.py")
    rel = "lint/registry.py"
    try:
        with open(path) as f:
            for i, text in enumerate(f, start=1):
                if re.match(rf"\s*{symbol}\b[^=]*=", text):
                    return rel, i
    except OSError:
        pass
    return rel, 0


def check_section_mirror(section: str, module_source: str,
                         module_path: str, defaults_symbol: str,
                         tuple_name: str,
                         structural: tuple = ()) -> list[Finding]:
    registered = set(getattr(reg, tuple_name)) - set(structural)
    defaults = _dict_literal_keys(module_source, defaults_symbol)
    if defaults is not None:
        defaults = defaults - set(structural)
    out: list[Finding] = []
    if defaults is None:
        out.append(finding(
            "registry-drift", module_path, 0,
            f"[{section}]: {defaults_symbol} dict literal not found "
            f"in {module_path} — the registry mirror "
            f"{tuple_name} cannot be recomputed"))
        return out
    rel, line = _registry_line(tuple_name)
    for k in sorted(defaults - registered):
        out.append(finding(
            "registry-drift", rel, line,
            f"[{section}] key {k!r} exists in {module_path} "
            f"{defaults_symbol} but not in registry.{tuple_name} — "
            f"configs setting it would be rejected as unknown"))
    for k in sorted(registered - defaults):
        out.append(finding(
            "registry-drift", rel, line,
            f"registry.{tuple_name} declares {k!r} but {module_path} "
            f"{defaults_symbol} does not define it — the registry "
            f"mirror drifted ahead of the schema"))
    return out


def _adapter_arg_keys(source: str) -> dict[str, tuple[int, set[str]]]:
    """kind -> (class line, args keys consumed) for every @register'd
    adapter: `args.get("k")`, `args["k"]`, `args.pop("k")`."""
    from .contracts import _is_registered
    tree = ast.parse(source)
    out: dict[str, tuple[int, set[str]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        kind = _is_registered(node)
        if kind is None:
            continue
        keys: set[str] = set()
        for n in ast.walk(node):
            if isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Attribute) and \
                    n.func.attr in ("get", "pop") and \
                    isinstance(n.func.value, ast.Name) and \
                    n.func.value.id == "args" and n.args and \
                    isinstance(n.args[0], ast.Constant) and \
                    isinstance(n.args[0].value, str):
                keys.add(n.args[0].value)
            elif isinstance(n, ast.Subscript) and \
                    isinstance(n.value, ast.Name) and \
                    n.value.id == "args" and \
                    isinstance(n.slice, ast.Constant) and \
                    isinstance(n.slice.value, str):
                keys.add(n.slice.value)
        out[kind] = (node.lineno, keys)
    return out


def check_adapter_registry(source: str, path: str) -> list[Finding]:
    """TILE_ARGS vs the keys the adapters actually read — both
    directions, EXTERNAL_ARG_KEYS exempting config-side consumers."""
    out: list[Finding] = []
    consumed = _adapter_arg_keys(source)
    rel, tline = _registry_line("TILE_ARGS")
    for kind, (line, keys) in sorted(consumed.items()):
        registered = set(reg.TILE_ARGS.get(kind, ()))
        known = registered | set(reg.COMMON_KEYS)
        for k in sorted(keys - known):
            out.append(finding(
                "registry-drift", path, line,
                f"adapter kind {kind!r} reads args[{k!r}] but "
                f"registry.TILE_ARGS does not declare it — configs "
                f"setting it would be rejected as unknown"
                f"{reg.suggest(k, known)}"))
        external = set(reg.EXTERNAL_ARG_KEYS.get(kind, ()))
        for k in sorted(registered - keys - external):
            out.append(finding(
                "registry-drift", rel, tline,
                f"registry.TILE_ARGS[{kind!r}] declares {k!r} but the "
                f"adapter never reads it — drop it or add it to "
                f"EXTERNAL_ARG_KEYS with its config-side consumer"))
    return out


def lint_registry_drift(
        sources: dict[str, str] | None = None) -> list[Finding]:
    """Tree-level registry-drift pass: adapter args + every section
    mirror. `sources` (path suffix -> source) overrides file reads for
    fixtures."""
    root = pkg_root()

    def read(suffix: str) -> str:
        if sources is not None and suffix in sources:
            return sources[suffix]
        try:
            with open(os.path.join(root, *suffix.split("/"))) as f:
                return f.read()
        except OSError:
            return ""
    out: list[Finding] = []
    adapters = read(_ADAPTERS_SUFFIX)
    if adapters:
        out.extend(check_adapter_registry(adapters, _ADAPTERS_SUFFIX))
    for section, suffix, defaults, tuple_name, structural in \
            SECTION_MIRRORS:
        src = read(suffix)
        if src:
            out.extend(check_section_mirror(
                section, src, suffix, defaults, tuple_name, structural))
    filtered: list[Finding] = []
    for f in out:
        src = read(f.path) if f.path.endswith(".py") and \
            "/" in f.path else ""
        filtered.extend(filter_suppressed([f], src))
    return filtered
