"""Tests for the in-repo static-analysis suite (tools/analysis).

Two layers of guarantee:

1. **Seeded violations**: for every checker, fixtures carrying a deliberate
   violation of each drift/violation class must FIRE. The wire-drift
   fixtures are mutated copies of the REAL protocol.h / wire.py (changed
   field width, reordered field, missing Priority value, drifted opcode,
   missing struct, header-layout drift), so the parser is exercised
   against production text, not toy grammars.
2. **Clean tree**: `python -m tools.analysis --all` exits 0 on the
   repository as committed — the acceptance gate CI's `analysis` job runs.

Plus the framework mechanics: inline `# its: allow[ID]` suppressions,
the committed-baseline flow, and machine-readable JSON output.
"""

import dataclasses
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analysis import (  # noqa: E402
    core,
    counters,
    loop_block,
    modelcheck,
    policy,
    races,
    trace_stages,
    wire_drift,
)
from tools.analysis import specs as mspecs  # noqa: E402
from tools.analysis.specs import membership_spec, ring_spec  # noqa: E402


def make_tree(tmp_path, files):
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)
    return core.Context(str(tmp_path))


# ---------------------------------------------------------------------------
# wire_drift (ITS-W*)
# ---------------------------------------------------------------------------

def drifted_ctx(tmp_path, header_sub=None, wire_sub=None, wire_append=""):
    """Context over copies of the real protocol.h / wire.py with one
    targeted mutation applied (asserting the anchor text exists, so a
    refactor that moves it fails loudly here instead of silently testing
    nothing)."""
    hdr = (REPO / wire_drift.HEADER_REL).read_text()
    wr = (REPO / wire_drift.WIRE_REL).read_text()
    if header_sub is not None:
        old, new = header_sub
        assert old in hdr, f"fixture anchor missing from protocol.h: {old!r}"
        hdr = hdr.replace(old, new, 1)
    if wire_sub is not None:
        old, new = wire_sub
        assert old in wr, f"fixture anchor missing from wire.py: {old!r}"
        wr = wr.replace(old, new, 1)
    wr += wire_append
    return make_tree(tmp_path, {wire_drift.HEADER_REL: hdr, wire_drift.WIRE_REL: wr})


class TestWireDrift:
    def test_real_tree_is_clean(self):
        assert wire_drift.compare(core.Context(str(REPO))) == []

    def test_parser_inventory(self):
        """The parsers must see the full protocol surface — a parser that
        silently skips half the header would also 'find no drift'."""
        ctx = core.Context(str(REPO))
        cpp = wire_drift.parse_header(ctx)
        py = wire_drift.parse_wire(ctx)
        ops = [k for k in cpp.constants if k.startswith("OP_")]
        assert len(ops) == 18
        assert len([k for k in cpp.constants if k.startswith("STATUS_")]) == 10
        assert cpp.constants["STATUS_COLD_TIER"] == 512
        assert cpp.constants["PRIORITY_BACKGROUND"] == 1
        assert cpp.header_asserts == {
            "ReqHeader": 9, "RespHeader": 16,
            "RingCtrl": 72, "RingSlot": 24, "RingCqe": 32,
            "RingBatchHdr": 4, "RingBatchEntry": 8,
        }
        for name in ("BatchMeta", "SegBatchMeta", "ShmLocResp", "SegMeta",
                     "RingMeta", "TcpPutMeta", "TicketMeta", "KeyMeta",
                     "KeyListMeta"):
            assert name in cpp.structs and name in py.structs
        # The mapped ring structs are parsed on BOTH representations: packed
        # width sequences (W004) and named-field layouts (W005).
        for name in ("RingCtrl", "RingSlot", "RingCqe", "RingBatchHdr",
                     "RingBatchEntry"):
            assert name in cpp.headers and name in py.headers
            assert name in py.ring_layouts
            assert py.ring_layouts[name] == [
                (f, {1: "u8", 2: "u16", 4: "u32", 8: "u64"}[w])
                for f, w in cpp.headers[name]
            ]
        # The QoS tag is an OPTIONAL trailing byte on both batch metas,
        # followed by the OPTIONAL trace-context pair (trace id + parent).
        assert cpp.structs["BatchMeta"][-3:] == ["u8?", "u64?", "u64?"]
        assert py.structs["BatchMeta"][-3:] == ["u8?", "u64?", "u64?"]
        assert cpp.structs["SegBatchMeta"][-3:] == ["u8?", "u64?", "u64?"]

    def test_changed_field_width_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, header_sub=(
            "w.u32(block_size);\n        w.str_list(keys);",
            "w.u16(block_size);\n        w.str_list(keys);",
        ))
        rules = {(f.rule, "BatchMeta" in f.message) for f in wire_drift.compare(ctx)}
        assert ("ITS-W002", True) in rules

    def test_reordered_field_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, header_sub=(
            "w.u32(block_size);\n        w.u16(seg_id);",
            "w.u16(seg_id);\n        w.u32(block_size);",
        ))
        found = [f for f in wire_drift.compare(ctx) if f.rule == "ITS-W002"]
        assert any("SegBatchMeta" in f.message for f in found)

    def test_missing_priority_value_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, header_sub=(
            "kPriorityBackground = 1,", "",
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W001" and "PRIORITY_BACKGROUND" in f.message
            for f in found
        )

    def test_opcode_value_drift_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, wire_sub=(
            'OP_STAT = ord("S")', 'OP_STAT = ord("T")',
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W001" and "OP_STAT" in f.message for f in found
        )

    def test_missing_struct_mirror_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, wire_sub=(
            "class TicketMeta:", "class TicketMetaRenamed:",
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W003" and "TicketMeta" in f.message for f in found
        )

    def test_fixed_header_drift_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, wire_sub=(
            '_REQ_HEADER = struct.Struct("<IBI")',
            '_REQ_HEADER = struct.Struct("<IBH")',
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W004" and "ReqHeader" in f.message for f in found
        )

    def test_header_static_assert_drift_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, header_sub=(
            "uint32_t body_size;\n};\nstruct RespHeader",
            "uint16_t body_size;\n};\nstruct RespHeader",
        ))
        found = wire_drift.compare(ctx)
        assert any(f.rule == "ITS-W004" for f in found)

    def test_python_only_struct_is_caught(self, tmp_path):
        """The diff is bidirectional: a wire-encoding dataclass added only
        to wire.py (not registered as client-side framing) must fire —
        the native server could never parse its bytes."""
        ctx = drifted_ctx(tmp_path, wire_append=(
            "\n\n@dataclass\nclass RogueMeta:\n"
            "    n: int = 0\n\n"
            "    def encode(self) -> bytes:\n"
            '        return struct.pack("<I", self.n)\n'
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W003" and "RogueMeta" in f.message for f in found
        )

    def test_python_only_header_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, wire_append=(
            '\n_ROGUE_HEADER = struct.Struct("<IQ")\n'
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W004" and "_ROGUE_HEADER" in f.message for f in found
        )

    def test_ring_same_width_field_swap_is_caught(self, tmp_path):
        """THE gap ITS-W005 exists for: swapping sq_tail/sq_head is
        invisible to the width diff (both u64) but misroutes every cursor
        access in mapped memory."""
        ctx = drifted_ctx(tmp_path, header_sub=(
            "uint64_t sq_tail;",
            "uint64_t sq_head_x;",
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W005" and "RingCtrl" in f.message for f in found
        )
        # And the width diff alone would indeed have stayed silent.
        assert not any(
            f.rule == "ITS-W004" and "RingCtrl" in f.message for f in found
        )

    def test_batch_entry_same_width_field_swap_is_caught(self, tmp_path):
        """Same gap, new struct: swapping the two u8s of a batch-slot entry
        (op <-> flags) keeps the width sequence AND the static_assert sum
        identical — only the named-field layout diff (W005) can see the
        server decoding every batched op's opcode from the flags byte."""
        ctx = drifted_ctx(tmp_path, header_sub=(
            # Anchored through RingBatchEntry's unique meta_len comment —
            # RingSlot carries byte-identical op/flags lines.
            "    uint32_t meta_len;  // SegBatchMeta bytes following this entry\n"
            "    uint8_t op;         // kOpPutFrom or kOpGetInto\n"
            "    uint8_t flags;      // reserved (0)",
            "    uint32_t meta_len;  // SegBatchMeta bytes following this entry\n"
            "    uint8_t flags;      // reserved (0)\n"
            "    uint8_t op;         // kOpPutFrom or kOpGetInto",
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W005" and "RingBatchEntry" in f.message for f in found
        )
        assert not any(
            f.rule == "ITS-W004" and "RingBatchEntry" in f.message for f in found
        )

    def test_ring_width_change_is_caught_by_both(self, tmp_path):
        ctx = drifted_ctx(tmp_path, header_sub=(
            "uint32_t meta_len;",
            "uint16_t meta_len;",
        ))
        rules = {f.rule for f in wire_drift.compare(ctx) if "RingSlot" in f.message}
        assert "ITS-W005" in rules
        assert "ITS-W004" in rules  # width sequence AND static_assert sum

    def test_ring_layout_removed_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, wire_sub=(
            '"RingCqe": (',
            '"RingCqeX": (',
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W005" and "RingCqe has no named-field" in f.message
            for f in found
        )
        assert any(
            f.rule == "ITS-W005" and "RingCqeX has no packed struct" in f.message
            for f in found
        )

    def test_ring_python_field_rename_is_caught(self, tmp_path):
        ctx = drifted_ctx(tmp_path, wire_sub=(
            '("token", "u64"),\n        ("meta_len", "u32"),',
            '("tok", "u64"),\n        ("meta_len", "u32"),',
        ))
        found = wire_drift.compare(ctx)
        assert any(
            f.rule == "ITS-W005" and "RingSlot" in f.message and "drifted" in f.message
            for f in found
        )

    def test_block_comment_preserves_line_anchors(self, tmp_path):
        """/* */ comments must not shift finding lines: suppression markers
        index into the ORIGINAL file."""
        ctx = drifted_ctx(tmp_path, header_sub=(
            "#pragma once",
            "/* a\n block\n comment\n */\n#pragma once",
        ))
        base = {
            k: v for k, v in wire_drift.parse_header(
                core.Context(str(REPO))).const_lines.items()
        }
        shifted = wire_drift.parse_header(ctx, wire_drift.HEADER_REL).const_lines
        # Original file line 14 is `#pragma once`; the fixture adds exactly
        # 4 lines before it, so every constant's anchor shifts by exactly 4.
        assert shifted["MAGIC"] == base["MAGIC"] + 4


# ---------------------------------------------------------------------------
# loop_block (ITS-L*)
# ---------------------------------------------------------------------------

LOOP_FIXTURE = '''\
import asyncio
import threading
import time


def helper():
    time.sleep(2)


async def direct():
    time.sleep(1)


async def transitive():
    helper()


async def escaped():
    await asyncio.to_thread(helper)


async def allowed():
    time.sleep(3)  # its: allow[ITS-L002]


class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.conn = None

    async def locked(self):
        with self._lock:
            pass

    async def native(self):
        lib.its_conn_connect(None)

    async def store(self):
        self.conn.read_cache([], 0, 0)
'''


class TestLoopBlock:
    @pytest.fixture()
    def fixture_ctx(self, tmp_path):
        return make_tree(tmp_path, {"pkg/mod.py": LOOP_FIXTURE})

    def test_seeded_violations_fire(self, fixture_ctx):
        found = loop_block.scan(fixture_ctx, package_rel="pkg", audited={})
        by_slug = {(f.rule, f.key.rsplit(":", 2)[-2:][0]) for f in found}
        # direct sleep in async body
        assert any(f.rule == "ITS-L002" and ":direct:" in f.key for f in found)
        # transitive through a sync helper, with the path in the message
        trans = [f for f in found if ":helper:" in f.key]
        assert trans and "transitive -> helper" in trans[0].message
        # lock acquire, native call, blocking store method
        assert any(f.rule == "ITS-L003" and "C.locked" in f.key for f in found)
        assert any(f.rule == "ITS-L001" and "its_conn_connect" in f.key for f in found)
        assert any(f.rule == "ITS-L001" and "read_cache" in f.key for f in found)
        del by_slug  # documented-above assertions are the contract

    def test_executor_hop_escapes(self, fixture_ctx):
        found = loop_block.scan(fixture_ctx, package_rel="pkg", audited={})
        # helper IS flagged via transitive(); the to_thread reference in
        # escaped() must not add an entry of its own (same site dedup) nor
        # flag escaped() itself.
        assert not any("escaped" in f.message for f in found)

    def test_inline_allow_suppresses(self, fixture_ctx):
        found = loop_block.scan(fixture_ctx, package_rel="pkg", audited={})
        allowed = [f for f in found if ":allowed:" in f.key]
        assert allowed  # the checker still SEES it...
        assert fixture_ctx.suppressed(allowed[0])  # ...but the marker wins

    def test_same_basename_modules_all_scanned(self, tmp_path):
        """Modules are keyed by path: two __init__.py (or same-named)
        files in different subpackages must BOTH be scanned."""
        bad = "import time\n\n\nasync def tick():\n    time.sleep(1)\n"
        ctx = make_tree(tmp_path, {
            "pkg/a/__init__.py": bad,
            "pkg/b/__init__.py": bad,
        })
        found = loop_block.scan(ctx, package_rel="pkg", audited={})
        files = {f.file for f in found}
        assert files == {"pkg/a/__init__.py", "pkg/b/__init__.py"}

    def test_start_fetch_is_a_blocking_name(self, tmp_path):
        """start_fetch embeds a probe RTT; an un-hopped call in an async
        body must fire (the vllm phase-1 regression class)."""
        ctx = make_tree(tmp_path, {"pkg/m.py": (
            "async def wave(kv):\n"
            "    return kv.start_fetch([1, 2])\n"
        )})
        found = loop_block.scan(ctx, package_rel="pkg", audited={})
        assert any(
            f.rule == "ITS-L001" and "start_fetch" in f.key for f in found
        )

    def test_audited_fg_gate_seed_is_active(self):
        """The committed allowlist must cover exactly the audited QoS
        foreground gate in lib.py: with the seed the real tree is clean,
        without it the gate's condition-variable ops surface."""
        ctx = core.Context(str(REPO))
        with_seed = loop_block.scan(ctx)
        assert not [f for f in with_seed if not ctx.suppressed(f)]
        bare = loop_block.scan(ctx, audited={})
        gate = [f for f in bare if "_fg_gate_" in f.key]
        assert gate, "fg gate sites should surface without the audit seed"


# ---------------------------------------------------------------------------
# counters (ITS-C*)
# ---------------------------------------------------------------------------

FIXTURE_CPP = '''
#include <string>
std::string Server::stats_json() {
    std::string out;
    out = "{\\"alpha\\":" + std::to_string(a_) +
          ",\\"grp\\":{\\"beta\\":" + std::to_string(b_) + "}" +
          ",\\"ops\\":{";
    for (const auto& [op, s] : stats_) {
        out += "\\"" + std::string(1, op) + "\\":{" +
               "\\"count\\":" + std::to_string(s.count) + "}";
    }
    out += "}}";
    return out;
}
'''

FIXTURE_MANAGE = '''
def _prometheus_text(stats):
    lines = [f"alpha {stats['alpha']}", f"gamma {stats['gamma']}"]
    for op, s in sorted(stats.get("ops", {}).items()):
        lines.append(f"count {s['count']}")
    return "\\n".join(lines)


def route(path):
    if path == "/stats":
        return get_server_stats()
'''


class TestCounters:
    @pytest.fixture()
    def fixture_ctx(self, tmp_path):
        return make_tree(tmp_path, {
            "native/server.cpp": FIXTURE_CPP,
            "manage.py": FIXTURE_MANAGE,
            "docs.md": "documented: alpha, count, gamma.\n",
        })

    def run_scan(self, ctx):
        return counters.scan(
            ctx, server_cpp_rel="native/server.cpp", manage_rel="manage.py",
            docs_rel="docs.md", ledgers=[],
        )

    def test_native_key_tree(self, fixture_ctx):
        keys = counters.native_stats_keys(fixture_ctx, "native/server.cpp")
        assert keys == {"alpha", "grp.beta", "ops.*.count"}

    def test_unexported_and_stale_keys_fire(self, fixture_ctx):
        found = self.run_scan(fixture_ctx)
        rules = {(f.rule, f.key.rsplit(":", 1)[-1]) for f in found}
        assert ("ITS-C001", "grp.beta") in rules      # native, not exported
        assert ("ITS-C002", "gamma") in rules         # exported, not native
        assert any(r == "ITS-C003" and k == "grp.beta" for r, k in rules)

    def test_missing_stats_route_fires(self, tmp_path):
        ctx = make_tree(tmp_path, {
            "native/server.cpp": FIXTURE_CPP,
            "manage.py": FIXTURE_MANAGE.replace('"/stats"', '"/nope"'),
            "docs.md": "alpha beta count gamma",
        })
        found = counters.scan(
            ctx, server_cpp_rel="native/server.cpp", manage_rel="manage.py",
            docs_rel="docs.md", ledgers=[],
        )
        assert any(f.rule == "ITS-C004" for f in found)

    def test_ledger_keys_doc_checked(self, tmp_path):
        ctx = make_tree(tmp_path, {
            "native/server.cpp": FIXTURE_CPP,
            "manage.py": FIXTURE_MANAGE,
            "docs.md": "alpha count gamma grp beta documented_key",
            "led.py": (
                "class K:\n"
                "    def stats(self):\n"
                "        return {'documented_key': 1, 'mystery_key': 2}\n"
            ),
        })
        found = counters.scan(
            ctx, server_cpp_rel="native/server.cpp", manage_rel="manage.py",
            docs_rel="docs.md", ledgers=[("led.py", "K.stats")],
        )
        ledger = [f for f in found if "K.stats" in f.key]
        assert any("mystery_key" in f.key for f in ledger)
        assert not any("documented_key" in f.key for f in ledger)

    def test_real_tree_is_clean(self):
        assert counters.scan(core.Context(str(REPO))) == []

    def test_real_native_inventory(self):
        """Pin the shape of the real stats_json parse: qos + spill + ops
        subtrees must all be seen (a parser regression that drops a subtree
        would otherwise pass 'clean')."""
        keys = counters.native_stats_keys(core.Context(str(REPO)))
        assert "qos.fg_ops" in keys and "spill.dropped" in keys
        assert "ops.*.p99_us" in keys and "conns_accepted" in keys


# ---------------------------------------------------------------------------
# policy (ITS-P*)
# ---------------------------------------------------------------------------

POLICY_FIXTURE = '''\
class InfiniStoreException(Exception):
    pass


def swallowed(conn):
    try:
        conn.op()
    except InfiniStoreException:
        pass


def routed(self, conn):
    try:
        conn.op()
    except InfiniStoreException as e:
        self._degrade([0], e)


def rethrown(conn):
    try:
        conn.op()
    except InfiniStoreException:
        raise


def semantic_ok(conn):
    try:
        conn.op()
    except InfiniStoreKeyNotFound:
        return 0


async def untagged(conn):
    await conn.write_cache_async([], 0, 0)


async def tagged(conn):
    await conn.write_cache_async([], 0, 0, priority=1)


async def splatted(conn, kw):
    await conn.read_cache_async([], 0, 0, **kw)
'''


class TestPolicy:
    @pytest.fixture()
    def fixture_ctx(self, tmp_path):
        return make_tree(tmp_path, {"pkg/mod.py": POLICY_FIXTURE})

    def test_seeded_violations_fire(self, fixture_ctx):
        found = policy.scan(fixture_ctx, package_rel="pkg",
                            p001_exempt=set(), p002_exempt=set())
        p1 = [f for f in found if f.rule == "ITS-P001"]
        p2 = [f for f in found if f.rule == "ITS-P002"]
        assert len(p1) == 1 and p1[0].line == POLICY_FIXTURE.splitlines().index(
            "    except InfiniStoreException:"
        ) + 1
        assert len(p2) == 1 and "write_cache_async" in p2[0].message

    def test_real_tree_is_clean_after_suppressions(self):
        ctx = core.Context(str(REPO))
        found = policy.scan(ctx)
        assert not [f for f in found if not ctx.suppressed(f)]


# ---------------------------------------------------------------------------
# framework: baseline, suppression classification, CLI, JSON
# ---------------------------------------------------------------------------

class TestFramework:
    def test_baseline_marks_known_findings(self, tmp_path):
        ctx = make_tree(tmp_path, {"pkg/mod.py": POLICY_FIXTURE})
        raw = policy.scan(ctx, package_rel="pkg",
                          p001_exempt=set(), p002_exempt=set())
        assert raw

        # A run() over a checker stub: everything baselined -> not failing.
        def stub(c):
            return policy.scan(c, package_rel="pkg",
                               p001_exempt=set(), p002_exempt=set())

        core.CHECKERS["_stub"] = core.Checker("_stub", "test stub", stub)
        try:
            baseline = {f.key: "audited in test" for f in raw}
            res = core.run(["_stub"], ctx=ctx, baseline=baseline)
            assert not res.failed and len(res.baselined) == len(raw)
            res2 = core.run(["_stub"], ctx=ctx, baseline={})
            assert res2.failed
        finally:
            del core.CHECKERS["_stub"]

    def test_stable_keys_do_not_move_with_unrelated_edits(self, tmp_path):
        ctx1 = make_tree(tmp_path / "a", {"pkg/mod.py": POLICY_FIXTURE})
        ctx2 = make_tree(
            tmp_path / "b",
            {"pkg/mod.py": "# unrelated leading comment\n\n" + POLICY_FIXTURE},
        )
        k1 = {f.key for f in policy.scan(ctx1, package_rel="pkg",
                                         p001_exempt=set(), p002_exempt=set())}
        k2 = {f.key for f in policy.scan(ctx2, package_rel="pkg",
                                         p001_exempt=set(), p002_exempt=set())}
        assert k1 == k2

    def test_cli_all_green_with_json(self, tmp_path):
        out = tmp_path / "analysis.json"
        proc = subprocess.run(
            [sys.executable, "-m", "tools.analysis", "--all", "--json", str(out)],
            cwd=str(REPO), capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(out.read_text())
        assert payload["failed"] is False
        assert set(payload["per_checker"]) == {
            "counters", "loop_block", "modelcheck", "policy", "races",
            "trace_stages", "wire_drift",
        }
        assert payload["counts"]["new"] == 0
        # Per-rule-family drift rows: every checker reports its finding
        # counts AND wall-clock, so the CI receipt shows which family is
        # growing (the bench-receipt pattern).
        for name, row in payload["per_checker"].items():
            assert set(row) == {"new", "baselined", "suppressed", "ms"}, name
            assert row["ms"] >= 0.0
        # The receipt carries modelcheck's per-spec exploration stats
        # (state counts + wall-time), so budget regressions show in CI.
        spec_rows = payload["stats"]["modelcheck"]["specs"]
        assert len(spec_rows) == 4
        for name, row in spec_rows.items():
            assert row["states"] > 0 and row["complete"], name
            assert row["ms"] >= 0.0

    def test_cli_rejects_unknown_checker(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.analysis", "nonsense"],
            cwd=str(REPO), capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 2

    def test_committed_baseline_is_loadable(self):
        baseline = core.load_baseline()
        assert isinstance(baseline, dict)

    def test_write_baseline_preserves_other_checkers_entries(self, tmp_path):
        """Baselining one checker's findings must not drop another
        checker's audited entries (prune is scoped to the ran checkers'
        rule prefixes)."""
        path = str(tmp_path / "baseline.json")
        core.write_baseline(
            [core.Finding(rule="ITS-L001", file="a.py", line=1,
                          message="m", key="ITS-L001:a.py:f")],
            path=path, prune_prefixes=None,
        )
        # A policy-only rewrite: the loop_block entry must survive.
        core.write_baseline(
            [core.Finding(rule="ITS-P001", file="b.py", line=1,
                          message="m", key="ITS-P001:b.py:g")],
            path=path, prune_prefixes=["ITS-P"],
        )
        entries = core.load_baseline(path)
        assert "ITS-L001:a.py:f" in entries and "ITS-P001:b.py:g" in entries
        # A full rewrite (prune everything) drops stale entries.
        core.write_baseline([], path=path, prune_prefixes=None)
        assert core.load_baseline(path) == {}

    def test_baseline_path_follows_root(self, tmp_path):
        """--root runs must use THAT tree's baseline, not this repo's."""
        ctx = core.Context(str(tmp_path))
        assert ctx.baseline_path.startswith(str(tmp_path))

    def test_policy_keys_anchor_on_enclosing_scope(self, tmp_path):
        """Adding a violation in one function must not re-key another
        function's baseline entry (the unsound-baseline failure mode)."""
        ctx1 = make_tree(tmp_path / "a", {"pkg/mod.py": POLICY_FIXTURE})
        extra = POLICY_FIXTURE.replace(
            "def swallowed(conn):",
            "def earlier(conn):\n"
            "    try:\n"
            "        conn.op()\n"
            "    except InfiniStoreException:\n"
            "        pass\n\n\n"
            "def swallowed(conn):",
        )
        ctx2 = make_tree(tmp_path / "b", {"pkg/mod.py": extra})
        k1 = {f.key for f in policy.scan(ctx1, package_rel="pkg",
                                         p001_exempt=set(), p002_exempt=set())}
        k2 = {f.key for f in policy.scan(ctx2, package_rel="pkg",
                                         p001_exempt=set(), p002_exempt=set())}
        assert k1 <= k2  # old keys intact; the new function adds its own
        assert any("earlier" in k for k in k2 - k1)

    def test_cli_write_baseline_also_writes_json(self, tmp_path):
        out = tmp_path / "analysis.json"
        baseline_file = REPO / "tools" / "analysis" / "baseline.json"
        snapshot = baseline_file.read_text()
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "tools.analysis", "--all",
                 "--json", str(out), "--write-baseline"],
                cwd=str(REPO), capture_output=True, text=True, timeout=120,
            )
            assert proc.returncode == 0, proc.stdout + proc.stderr
            assert json.loads(out.read_text())["counts"]["new"] == 0
        finally:
            baseline_file.write_text(snapshot)  # the test must not mutate the repo


# ---------------------------------------------------------------------------
# policy ITS-P003: migration traffic is BACKGROUND (membership subsystem)
# ---------------------------------------------------------------------------

P003_FIXTURE = '''\
from .wire import PRIORITY_BACKGROUND, PRIORITY_FOREGROUND
import wire


def copy_ok(src, dst, blocks, size, ptr):
    src.read_cache(blocks, size, ptr, priority=PRIORITY_BACKGROUND)
    dst.write_cache(blocks, size, ptr, priority=wire.PRIORITY_BACKGROUND)
    dst.write_cache(blocks, size, ptr, **wire.qos_kwargs(dst, PRIORITY_BACKGROUND))
    src.tcp_read_cache("k", priority=PRIORITY_BACKGROUND)


def copy_untagged(src, blocks, size, ptr):
    src.read_cache(blocks, size, ptr)


def copy_foreground(dst, blocks, size, ptr):
    dst.write_cache(blocks, size, ptr, priority=PRIORITY_FOREGROUND)


def copy_tcp_untagged(src, dst):
    data = src.tcp_read_cache("k")
    dst.tcp_write_cache("k", 0, 16)
'''


class TestPolicyP003:
    def scan(self, tmp_path):
        ctx = make_tree(tmp_path, {"pkg/membership.py": P003_FIXTURE})
        return policy.scan(
            ctx, package_rel="pkg", p001_exempt=set(), p002_exempt=set(),
            p003_files={"pkg/membership.py"},
        )

    def test_untagged_and_foreground_migration_ops_fire(self, tmp_path):
        p3 = [f for f in self.scan(tmp_path) if f.rule == "ITS-P003"]
        ops = sorted(f.message.split("(")[0].split(".")[1].split("()")[0] for f in p3)
        # The three violations: an untagged batched read, a FOREGROUND-tagged
        # batched write, and BOTH untagged single-key tcp ops.
        assert ops == [
            "read_cache", "tcp_read_cache", "tcp_write_cache", "write_cache",
        ]

    def test_background_tagged_calls_pass(self, tmp_path):
        p3 = [f for f in self.scan(tmp_path) if f.rule == "ITS-P003"]
        # Nothing from copy_ok: kwarg, attribute form, and qos_kwargs splat
        # all count as a BACKGROUND tag.
        assert not [f for f in p3 if "copy_ok" in f.key]

    def test_scope_is_membership_only(self, tmp_path):
        ctx = make_tree(tmp_path, {"pkg/other.py": P003_FIXTURE})
        found = policy.scan(
            ctx, package_rel="pkg", p001_exempt=set(), p002_exempt=set(),
            p003_files={"pkg/membership.py"},
        )
        assert not [f for f in found if f.rule == "ITS-P003"]

    def test_real_membership_is_background_tagged(self):
        ctx = core.Context(str(REPO))
        found = [f for f in policy.scan(ctx) if f.rule == "ITS-P003"]
        assert found == []

    def test_tiering_is_in_p003_scope(self, tmp_path):
        # The tiered capacity plane's copy engine (docs/tiering.md) is
        # migration traffic too: an untagged op in tiering.py fires.
        ctx = make_tree(tmp_path, {"pkg/tiering.py": P003_FIXTURE})
        found = policy.scan(
            ctx, package_rel="pkg", p001_exempt=set(), p002_exempt=set(),
            p003_files=policy.P003_FILES | {"pkg/tiering.py"},
        )
        assert [f for f in found if f.rule == "ITS-P003"]
        assert "infinistore_tpu/tiering.py" in policy.P003_FILES


# ---------------------------------------------------------------------------
# counters ITS-C005: membership status keys reach the /metrics exporter
# ---------------------------------------------------------------------------

C005_MEMBERSHIP = '''\
class Membership:
    def status(self):
        return {"membership_epoch": 1, "membership_settled": 1}


class Resharder:
    def __init__(self):
        self._c = {"reshard_moved_roots": 0, "reshard_debt_roots": 0}

    def progress(self):
        out = dict(self._c)
        out["reshard_active"] = 0
        return out


class DurableLog:
    def status(self):
        return {"journal_records": 0, "journal_replay_torn": 0}
'''

C005_MANAGE_OK = '''\
def _membership_prometheus_lines(ms):
    return [
        f"a {ms['membership_epoch']}",
        f"b {ms['membership_settled']}",
        f"c {ms['reshard_moved_roots']}",
        f"d {ms['reshard_debt_roots']}",
        f"e {ms['reshard_active']}",
        f"f {ms.get('journal_records', 0)}",
        f"g {ms.get('journal_replay_torn', 0)}",
    ]

route = "/membership"   # served from membership_status
route2 = "/bootstrap"   # served from bootstrap_payload
'''


class TestCountersMembership:
    def scan(self, tmp_path, manage_src, membership_src=C005_MEMBERSHIP):
        ctx = make_tree(tmp_path, {
            "manage.py": manage_src, "membership.py": membership_src,
        })
        return counters._scan_membership(ctx, "manage.py", "membership.py")

    def test_complete_exporter_is_clean(self, tmp_path):
        assert self.scan(tmp_path, C005_MANAGE_OK) == []

    def test_unexported_status_key_fires(self, tmp_path):
        manage = C005_MANAGE_OK.replace(
            "        f\"d {ms['reshard_debt_roots']}\",\n", "")
        found = self.scan(tmp_path, manage)
        assert any(
            f.rule == "ITS-C005" and f.key.endswith("reshard_debt_roots")
            for f in found
        )

    def test_stale_exporter_key_fires(self, tmp_path):
        manage = C005_MANAGE_OK.replace(
            "reshard_debt_roots", "reshard_gone_key")
        found = self.scan(tmp_path, manage)
        keys = {f.key for f in found}
        assert any(k.endswith("stale:reshard_gone_key") for k in keys)
        assert any(k.endswith(":reshard_debt_roots") for k in keys)

    def test_missing_membership_route_fires(self, tmp_path):
        manage = C005_MANAGE_OK.replace('"/membership"', '"/nope"')
        found = self.scan(tmp_path, manage)
        assert any(f.key.endswith("membership-route") for f in found)

    def test_unexported_journal_key_fires(self, tmp_path):
        manage = C005_MANAGE_OK.replace(
            "        f\"g {ms.get('journal_replay_torn', 0)}\",\n", "")
        found = self.scan(tmp_path, manage)
        assert any(
            f.rule == "ITS-C005" and f.key.endswith("journal_replay_torn")
            for f in found
        )

    def test_missing_bootstrap_route_fires(self, tmp_path):
        manage = C005_MANAGE_OK.replace("bootstrap_payload", "nothing")
        found = self.scan(tmp_path, manage)
        assert any(f.key.endswith("bootstrap-route") for f in found)

    def test_real_membership_counters_are_clean(self):
        ctx = core.Context(str(REPO))
        found = [f for f in counters.scan(ctx) if f.rule == "ITS-C005"]
        assert found == []


# ---------------------------------------------------------------------------
# counters ITS-C006: fleet-telemetry vocabulary lockstep
# ---------------------------------------------------------------------------

C006_TELEMETRY = '''\
EVENT_KINDS = (
    "breaker_open",
    "membership_epoch",
)


class SloEngine:
    def status(self):
        return {
            "slo_availability": 1.0,
            "slo_burn_rate_max": 0.0,
            "verdict": "ok",
        }


class GossipAgent:
    def status(self):
        return {"gossip_rounds": 0, "gossip_merges_in": 0}


def emit(kind, **attrs):
    pass


emit("membership_epoch")
'''

C006_PRODUCER = '''\
from . import telemetry

telemetry.emit("breaker_open", member="m0")
'''

C006_MANAGE_OK = '''\
def _slo_prometheus_lines(slo):
    return [
        f"a {slo['slo_availability']}",
        f"b {slo['slo_burn_rate_max']}",
    ]


def _gossip_prometheus_lines(gs):
    return [
        f"a {gs['gossip_rounds']}",
        f"b {gs['gossip_merges_in']}",
    ]

route_a = "/slo"      # served from telemetry.slo_engine
route_b = "/events"   # served from telemetry.get_journal
route_c = "/gossip"   # served through cluster.merge_remote_view
served = (slo_engine, get_journal, merge_remote_view)
'''

C006_DOCS = (
    "table: breaker_open membership_epoch slo_availability "
    "slo_burn_rate_max gossip_rounds gossip_merges_in\n"
)


class TestCountersTelemetry:
    def scan(self, tmp_path, manage_src=C006_MANAGE_OK,
             telemetry_src=C006_TELEMETRY, producer_src=C006_PRODUCER,
             docs=C006_DOCS):
        ctx = make_tree(tmp_path, {
            "manage.py": manage_src,
            "pkg/telemetry.py": telemetry_src,
            "pkg/producer.py": producer_src,
            "docs/obs.md": docs,
        })
        return counters._scan_telemetry(
            ctx, "manage.py", telemetry_rel="pkg/telemetry.py",
            docs_rel="docs/obs.md", package_rel="pkg",
        )

    def test_complete_vocabulary_is_clean(self, tmp_path):
        assert self.scan(tmp_path) == []

    def test_unexported_slo_key_fires(self, tmp_path):
        manage = C006_MANAGE_OK.replace(
            "        f\"b {slo['slo_burn_rate_max']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(
            f.rule == "ITS-C006" and f.key.endswith("slo_burn_rate_max")
            for f in found
        )

    def test_stale_slo_exporter_key_fires(self, tmp_path):
        manage = C006_MANAGE_OK.replace(
            "slo_burn_rate_max", "slo_gone_key")
        keys = {f.key for f in self.scan(tmp_path, manage_src=manage)}
        assert any(k.endswith("stale:slo_gone_key") for k in keys)
        assert any(k.endswith(":slo_burn_rate_max") for k in keys)

    def test_undocumented_slo_key_fires(self, tmp_path):
        docs = C006_DOCS.replace("slo_availability", "")
        found = self.scan(tmp_path, docs=docs)
        assert any(
            f.key.endswith("undocumented:slo_availability") for f in found
        )

    def test_unknown_event_kind_fires_at_producer(self, tmp_path):
        producer = C006_PRODUCER.replace("breaker_open", "made_up_kind")
        found = self.scan(tmp_path, producer_src=producer)
        hits = [f for f in found if "unknown-kind:made_up_kind" in f.key]
        assert hits and hits[0].file == "pkg/producer.py"
        # ...and breaker_open is now dead vocabulary (no producer left).
        assert any(f.key.endswith("dead:breaker_open") for f in found)

    def test_undocumented_event_kind_fires(self, tmp_path):
        docs = C006_DOCS.replace("membership_epoch", "")
        found = self.scan(tmp_path, docs=docs)
        assert any(
            f.key.endswith("undocumented:membership_epoch") for f in found
        )

    def test_missing_slo_route_fires(self, tmp_path):
        manage = C006_MANAGE_OK.replace('"/slo"', '"/nope"')
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith("slo-route") for f in found)

    def test_missing_events_route_fires(self, tmp_path):
        manage = C006_MANAGE_OK.replace("get_journal", "no_journal")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith("events-route") for f in found)

    def test_unexported_gossip_key_fires(self, tmp_path):
        manage = C006_MANAGE_OK.replace(
            "        f\"b {gs['gossip_merges_in']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(
            f.rule == "ITS-C006" and f.key.endswith("gossip:gossip_merges_in")
            for f in found
        )

    def test_stale_gossip_exporter_key_fires(self, tmp_path):
        manage = C006_MANAGE_OK.replace("gossip_merges_in", "gossip_gone")
        keys = {f.key for f in self.scan(tmp_path, manage_src=manage)}
        assert any(k.endswith("gossip-stale:gossip_gone") for k in keys)
        assert any(k.endswith("gossip:gossip_merges_in") for k in keys)

    def test_undocumented_gossip_key_fires(self, tmp_path):
        docs = C006_DOCS.replace("gossip_rounds", "")
        found = self.scan(tmp_path, docs=docs)
        assert any(
            f.key.endswith("undocumented:gossip_rounds") for f in found
        )

    def test_missing_gossip_route_fires(self, tmp_path):
        manage = C006_MANAGE_OK.replace("merge_remote_view", "nothing")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith("gossip-route") for f in found)

    def test_real_telemetry_vocabulary_is_clean(self):
        ctx = core.Context(str(REPO))
        found = [f for f in counters.scan(ctx) if f.rule == "ITS-C006"]
        assert found == []


# ---------------------------------------------------------------------------
# counters ITS-C007: tiered-capacity-plane vocabulary lockstep
# ---------------------------------------------------------------------------

C007_TIERING = '''\
class TierManager:
    def __init__(self):
        self._c = {"tier_demotions": 0, "tier_cold_hits": 0}

    def status(self):
        return {**self._c, "tier_cold_members": 1, "tier_promote_backlog": 0}
'''

C007_MANAGE_OK = '''\
def _tier_prometheus_lines(ts):
    return [
        f"a {ts['tier_demotions']}",
        f"b {ts['tier_cold_hits']}",
        f"c {ts['tier_cold_members']}",
        f"d {ts['tier_promote_backlog']}",
    ]

route = "/tiers"   # served from the cluster's tiering status
'''

C007_DOCS = (
    "| tier_demotions | tier_cold_hits | tier_cold_members | "
    "tier_promote_backlog |\n"
)


class TestCountersTiering:
    def scan(self, tmp_path, manage_src=C007_MANAGE_OK,
             tiering_src=C007_TIERING, docs=C007_DOCS):
        ctx = make_tree(tmp_path, {
            "manage.py": manage_src,
            "tiering.py": tiering_src,
            "docs/tiering.md": docs,
        })
        return counters._scan_tiering(
            ctx, "manage.py", tiering_rel="tiering.py",
            docs_rel="docs/tiering.md",
        )

    def test_complete_vocabulary_is_clean(self, tmp_path):
        assert self.scan(tmp_path) == []

    def test_unexported_tier_key_fires(self, tmp_path):
        manage = C007_MANAGE_OK.replace(
            "        f\"b {ts['tier_cold_hits']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(
            f.rule == "ITS-C007" and f.key.endswith(":tier_cold_hits")
            for f in found
        )

    def test_unexported_init_ledger_key_fires(self, tmp_path):
        # Keys living only in the __init__ counter dict (not the status
        # literal) are vocabulary too — the C005 Resharder.__init__ rule.
        manage = C007_MANAGE_OK.replace(
            "        f\"a {ts['tier_demotions']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith(":tier_demotions") for f in found)

    def test_stale_exporter_key_fires(self, tmp_path):
        manage = C007_MANAGE_OK.replace("tier_cold_hits", "tier_gone_key")
        keys = {f.key for f in self.scan(tmp_path, manage_src=manage)}
        assert any(k.endswith("stale:tier_gone_key") for k in keys)
        assert any(k.endswith(":tier_cold_hits") for k in keys)

    def test_undocumented_tier_key_fires(self, tmp_path):
        docs = C007_DOCS.replace("tier_cold_members", "")
        found = self.scan(tmp_path, docs=docs)
        assert any(
            f.key.endswith("undocumented:tier_cold_members") for f in found
        )

    def test_missing_tiers_route_fires(self, tmp_path):
        manage = C007_MANAGE_OK.replace('"/tiers"', '"/nope"').replace(
            "tiering", "nothing")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith("tiers-route") for f in found)

    def test_real_tiering_vocabulary_is_clean(self):
        ctx = core.Context(str(REPO))
        found = [f for f in counters.scan(ctx) if f.rule == "ITS-C007"]
        assert found == []


# ---------------------------------------------------------------------------
# counters ITS-C008: continuous-profiling / metrics-history lockstep
# ---------------------------------------------------------------------------

C008_PROFILING = '''\
class SamplingProfiler:
    def status(self):
        return {"prof_samples": 0, "prof_tagged_samples": 0, "prof_hz": 101.0}
'''

C008_TELEMETRY = '''\
class MetricsHistory:
    def status(self):
        return {"timeseries_series": 0, "timeseries_anomalies": 0}
'''

C008_MANAGE_OK = '''\
def _prof_prometheus_lines(ps):
    return [
        f"a {ps['prof_samples']}",
        f"b {ps['prof_tagged_samples']}",
        f"c {ps['prof_hz']}",
    ]


def _timeseries_prometheus_lines(ts):
    return [
        f"a {ts['timeseries_series']}",
        f"b {ts['timeseries_anomalies']}",
    ]

routes = ("/profile", "/timeseries")   # profiling + history surfaces
'''

C008_DOCS = (
    "| prof_samples | prof_tagged_samples | prof_hz | "
    "timeseries_series | timeseries_anomalies |\n"
)


class TestCountersProfiling:
    def scan(self, tmp_path, manage_src=C008_MANAGE_OK,
             profiling_src=C008_PROFILING, telemetry_src=C008_TELEMETRY,
             docs=C008_DOCS):
        ctx = make_tree(tmp_path, {
            "manage.py": manage_src,
            "profiling.py": profiling_src,
            "telemetry.py": telemetry_src,
            "docs/observability.md": docs,
        })
        return counters._scan_profiling(
            ctx, "manage.py", profiling_rel="profiling.py",
            telemetry_rel="telemetry.py", docs_rel="docs/observability.md",
        )

    def test_complete_vocabulary_is_clean(self, tmp_path):
        assert self.scan(tmp_path) == []

    def test_unexported_prof_key_fires(self, tmp_path):
        manage = C008_MANAGE_OK.replace(
            "        f\"b {ps['prof_tagged_samples']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(
            f.rule == "ITS-C008"
            and f.key.endswith("prof:prof_tagged_samples")
            for f in found
        )

    def test_stale_prof_exporter_key_fires(self, tmp_path):
        manage = C008_MANAGE_OK.replace("prof_tagged_samples", "prof_gone")
        keys = {f.key for f in self.scan(tmp_path, manage_src=manage)}
        assert any(k.endswith("prof-stale:prof_gone") for k in keys)
        assert any(k.endswith("prof:prof_tagged_samples") for k in keys)

    def test_unexported_timeseries_key_fires(self, tmp_path):
        manage = C008_MANAGE_OK.replace(
            "        f\"b {ts['timeseries_anomalies']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(
            f.key.endswith("timeseries:timeseries_anomalies") for f in found
        )

    def test_stale_timeseries_exporter_key_fires(self, tmp_path):
        manage = C008_MANAGE_OK.replace("timeseries_anomalies",
                                        "timeseries_gone")
        keys = {f.key for f in self.scan(tmp_path, manage_src=manage)}
        assert any(k.endswith("timeseries-stale:timeseries_gone")
                   for k in keys)

    def test_undocumented_keys_fire(self, tmp_path):
        docs = C008_DOCS.replace("prof_hz", "").replace(
            "timeseries_series", "")
        keys = {f.key for f in self.scan(tmp_path, docs=docs)}
        assert any(k.endswith("undocumented:prof_hz") for k in keys)
        assert any(k.endswith("undocumented:timeseries_series") for k in keys)

    def test_missing_profile_route_fires(self, tmp_path):
        manage = C008_MANAGE_OK.replace('"/profile"', '"/nope"')
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith("profile-route") for f in found)

    def test_missing_timeseries_route_fires(self, tmp_path):
        manage = C008_MANAGE_OK.replace('"/timeseries"', '"/nope"').replace(
            "history", "nothing")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith("timeseries-route") for f in found)

    def test_real_profiling_vocabulary_is_clean(self):
        ctx = core.Context(str(REPO))
        found = [f for f in counters.scan(ctx) if f.rule == "ITS-C008"]
        assert found == []


# ---------------------------------------------------------------------------
# trace_stages (ITS-T*)
# ---------------------------------------------------------------------------

T_TRACING = '''\
STAGES = (
    "enqueue",
    "submit",
    "server_recv",
)

SERVER_TICK_STAGES = {
    "recv_us": "server_recv",
}
'''

T_PRODUCER = '''\
def run(span, tracing):
    span.stage("enqueue")
    with tracing.trace_op("op", stage="submit"):
        pass
'''

T_MANAGE = '''\
def _trace_payload(stats):
    return {"stages": list(STAGES)}


def route(path):
    if path == "/trace":
        return _trace_payload({})
'''

T_CPP = '''\
void Server::stats_json() {
    out += ",\\"recv_us\\":" + std::to_string(t.recv_us);
}
'''


class TestTraceStages:
    def _tree(self, tmp_path, **overrides):
        files = {
            "infinistore_tpu/tracing.py": T_TRACING,
            "infinistore_tpu/prod.py": T_PRODUCER,
            "infinistore_tpu/server.py": T_MANAGE,
            "docs/observability.md": "stages: enqueue submit server_recv\n",
            "native/src/server.cpp": T_CPP,
        }
        files.update(overrides)
        return make_tree(tmp_path, files)

    def test_clean_fixture(self, tmp_path):
        assert trace_stages.scan(self._tree(tmp_path)) == []

    def test_unknown_producer_stage_fires(self, tmp_path):
        ctx = self._tree(tmp_path, **{
            "infinistore_tpu/prod.py":
                T_PRODUCER.replace('"enqueue"', '"mystery_stage"'),
        })
        found = trace_stages.scan(ctx)
        assert any(
            f.rule == "ITS-T001" and "mystery_stage" in f.key for f in found
        )

    def test_trace_op_stage_kwarg_is_scanned(self, tmp_path):
        ctx = self._tree(tmp_path, **{
            "infinistore_tpu/prod.py":
                T_PRODUCER.replace('stage="submit"', 'stage="kw_rogue"'),
        })
        found = trace_stages.scan(ctx)
        assert any(
            f.rule == "ITS-T001" and "kw_rogue" in f.key for f in found
        )

    def test_undocumented_stage_fires(self, tmp_path):
        ctx = self._tree(
            tmp_path, **{"docs/observability.md": "stages: enqueue submit\n"}
        )
        found = trace_stages.scan(ctx)
        assert any(
            f.rule == "ITS-T002" and f.key.endswith("server_recv")
            for f in found
        )

    def test_missing_trace_route_fires(self, tmp_path):
        ctx = self._tree(tmp_path, **{
            "infinistore_tpu/server.py": T_MANAGE.replace('"/trace"', '"/nope"'),
        })
        found = trace_stages.scan(ctx)
        assert any(f.key.endswith("trace-route") for f in found)

    def test_tick_map_outside_vocabulary_fires(self, tmp_path):
        ctx = self._tree(tmp_path, **{
            "infinistore_tpu/tracing.py":
                T_TRACING.replace('"recv_us": "server_recv"',
                                  '"recv_us": "not_a_stage"'),
        })
        found = trace_stages.scan(ctx)
        assert any(
            f.rule == "ITS-T003" and f.key.endswith("tick:recv_us")
            for f in found
        )

    def test_native_tick_field_missing_fires(self, tmp_path):
        ctx = self._tree(
            tmp_path, **{"native/src/server.cpp": "void nothing() {}\n"}
        )
        found = trace_stages.scan(ctx)
        assert any(
            f.rule == "ITS-T003" and f.key.endswith("native:recv_us")
            for f in found
        )

    def test_dead_vocabulary_fires(self, tmp_path):
        ctx = self._tree(tmp_path, **{
            "infinistore_tpu/tracing.py": T_TRACING.replace(
                '"submit",', '"submit",\n    "never_stamped",'
            ),
            "docs/observability.md":
                "stages: enqueue submit server_recv never_stamped\n",
        })
        found = trace_stages.scan(ctx)
        assert any(
            f.rule == "ITS-T004" and f.key.endswith("dead:never_stamped")
            for f in found
        )

    def test_real_tree_is_clean_modulo_docs(self):
        """The real repo's producers, tick map, /trace schema and native
        emitter are in lockstep (T002 pends only on docs/observability.md
        existing — covered by the clean-suite acceptance test)."""
        found = [
            f for f in trace_stages.scan(core.Context(str(REPO)))
            if f.rule != "ITS-T002"
        ]
        assert found == []

    def test_real_vocabulary_inventory(self):
        stages, tick_map = trace_stages.recorder_stages(core.Context(str(REPO)))
        assert stages[0] == "enqueue" and "stripe_claim" in stages
        assert set(tick_map.values()) == {
            "server_recv", "first_slice", "last_slice",
        }


# ---------------------------------------------------------------------------
# races (ITS-R*): cross-thread shared-state discipline
# ---------------------------------------------------------------------------

def mutated_pkg(tmp_path, rel, sub=None, append=""):
    """Fixture tree holding a copy of ONE real package module with a
    targeted mutation (the wire-drift pattern: anchors must exist, so a
    refactor that moves them fails loudly instead of testing nothing)."""
    src = (REPO / rel).read_text()
    if sub is not None:
        old, new = sub
        assert old in src, f"fixture anchor missing from {rel}: {old!r}"
        src = src.replace(old, new, 1)
    src += append
    return make_tree(tmp_path, {rel: src})


class TestRaces:
    def test_real_tree_is_clean_after_suppressions(self):
        ctx = core.Context(str(REPO))
        found = races.scan(ctx)
        assert not [f for f in found if not ctx.suppressed(f)]

    def test_registry_classifies_the_daemon_owners(self):
        """The shared-state registry must see the known worker-thread
        owners (a regression that stops classifying them would also stop
        finding anything)."""
        ctx = core.Context(str(REPO))
        names = {sc.cls.name for sc in races.build_registry(ctx)}
        for expected in ("TierManager", "Resharder", "FleetScraper",
                         "GossipAgent", "Membership", "ClusterKVConnector",
                         "EventJournal", "DurableLog"):
            assert expected in names, expected

    # -- R001: guard discipline over mutated REAL sources -------------------

    def test_removed_guard_annotation_fires(self, tmp_path):
        """Deleting the `guard[_c: _stats_lock]` declaration re-exposes
        the confirmed PR 13 race: TierManager._c is written on both sides
        with no declared guard."""
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/tiering.py",
            sub=("# its: guard[_c: _stats_lock]", "#"),
        )
        found = races.scan(ctx, docs=False)
        assert any(
            f.rule == "ITS-R001" and f.key.endswith("TierManager._c")
            for f in found
        )

    def test_access_outside_declared_guard_fires(self, tmp_path):
        """Stripping the lock out of _bump (the declared guard stays)
        must fire the dominance check on the bare write."""
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/tiering.py",
            sub=(
                "        with self._stats_lock:\n            self._c[key] += n",
                "        if True:\n            self._c[key] += n",
            ),
        )
        found = races.scan(ctx, docs=False)
        hits = [
            f for f in found
            if f.rule == "ITS-R001" and "TierManager._c" in f.key
            and "_bump" in f.key
        ]
        assert hits and "outside its declared guard" in hits[0].message

    def test_single_writer_violation_fires(self, tmp_path):
        """A single_writer ledger written from BOTH sides is a lie: seed a
        loop-side write into Resharder (declared single_writer) and the
        checker must fire."""
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/membership.py",
            sub=(
                "    def kick(self):\n        \"\"\"Wake the reconciler",
                "    def kick(self):\n"
                "        self._c[\"reshard_passes\"] += 0  # seeded\n"
                "        \"\"\"Wake the reconciler",
            ),
        )
        found = races.scan(ctx, docs=False)
        assert any(
            f.rule == "ITS-R001" and "Resharder._c" in f.key
            and "single-writer" in f.key
            for f in found
        )

    # -- R002: lock-order cycles --------------------------------------------

    def test_inverted_lock_order_fires(self, tmp_path):
        """add_member nests _cat_lock under _admin_lock; appending one
        function taking them in the OPPOSITE order closes a deadlock
        cycle the graph must report."""
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/cluster.py",
            append=(
                "\n\ndef _seeded_inversion(self):\n"
                "    with self._cat_lock:\n"
                "        with self._admin_lock:\n"
                "            pass\n"
            ),
        )
        found = races.scan(ctx, docs=False)
        cycles = [f for f in found if f.rule == "ITS-R002" and "cycle" in f.key]
        assert cycles and any(
            "_admin_lock" in f.message and "_cat_lock" in f.message
            for f in cycles
        )

    def test_reacquiring_a_plain_lock_fires(self, tmp_path):
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/cluster.py",
            append=(
                "\n\ndef _seeded_reacquire(self):\n"
                "    with self._cat_lock:\n"
                "        with self._cat_lock:\n"
                "            pass\n"
            ),
        )
        found = races.scan(ctx, docs=False)
        assert any(
            f.rule == "ITS-R002" and "reacquire" in f.key for f in found
        )

    def test_real_lock_order_graph_is_acyclic(self):
        idx = races.PackageIndex(core.Context(str(REPO)))
        edges = races.lock_order_edges(idx)
        assert races.find_cycles(edges) == []
        # The blessed journal-compaction direction is in the graph (the
        # `its: acquires[...]` summary; the tracer validates it live).
        assert ("DurableLog._lock", "ClusterKVConnector._cat_lock") in edges

    # -- R003: journal/emit outside engine locks -----------------------------

    def test_journal_under_catalog_lock_fires(self, tmp_path):
        """Moving catalog_add_holder's journal append INSIDE the catalog
        lock breaks the emit-outside-lock discipline structurally."""
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/cluster.py",
            sub=(
                "            rec.holders[member_id] = "
                "max(rec.holders.get(member_id, 0), blocks)\n",
                "            rec.holders[member_id] = "
                "max(rec.holders.get(member_id, 0), blocks)\n"
                "            self._journal_append({\"k\": \"seeded\"})\n",
            ),
        )
        found = races.scan(ctx, docs=False)
        hits = [
            f for f in found
            if f.rule == "ITS-R003" and "catalog_add_holder" in f.key
        ]
        assert hits and "_cat_lock" in hits[0].message

    def test_real_tree_honors_emit_discipline(self):
        ctx = core.Context(str(REPO))
        idx = races.PackageIndex(ctx)
        assert races.check_r003(ctx, idx) == []

    # -- R004: predicate-looped condition waits ------------------------------

    def test_bare_if_gated_wait_fires(self, tmp_path):
        """Regressing TierManager._run to its pre-PR-13 `if`-gated wait
        (acting on a possibly-spurious wake) must fire."""
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/tiering.py",
            sub=(
                "                while not self._dirty and not self._stop:\n"
                "                    if not self._cv.wait(timeout=self.interval_s):\n"
                "                        break",
                "                if not self._dirty and not self._stop:\n"
                "                    self._cv.wait(timeout=self.interval_s)",
            ),
        )
        found = races.scan(ctx, docs=False)
        assert any(
            f.rule == "ITS-R004" and "TierManager._cv" in f.message
            for f in found
        )

    def test_wait_for_and_event_waits_are_exempt(self, tmp_path):
        ctx = make_tree(tmp_path, {"infinistore_tpu/m.py": (
            "import threading\n\n\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "        self._ev = threading.Event()\n"
            "        self._thread = None\n\n"
            "    def start(self):\n"
            "        self._thread = threading.Thread(target=self._run)\n\n"
            "    def _run(self):\n"
            "        with self._cv:\n"
            "            self._cv.wait_for(lambda: True)\n"
            "        self._ev.wait(1.0)\n"
        )})
        found = races.scan(ctx, docs=False)
        assert not [f for f in found if f.rule == "ITS-R004"]

    # -- R005: concurrency-model docs lockstep -------------------------------

    def test_real_docs_table_is_in_lockstep(self):
        ctx = core.Context(str(REPO))
        idx = races.PackageIndex(ctx)
        assert races.check_r005(ctx, idx) == []

    def test_missing_docs_row_fires(self, tmp_path):
        src = (REPO / "infinistore_tpu/tiering.py").read_text()
        ctx = make_tree(tmp_path, {
            "infinistore_tpu/tiering.py": src,
            "docs/design.md": "# design\n\nno table here\n",
        })
        found = races.check_r005(ctx, races.PackageIndex(ctx))
        assert any(
            f.rule == "ITS-R005" and "TierManager._c" in f.key for f in found
        )

    def test_stale_docs_row_fires(self, tmp_path):
        ctx = core.Context(str(REPO))
        doc = (REPO / "docs/design.md").read_text() + (
            "\n| `GhostClass._gone` | `_lock` | all accesses | "
            "`infinistore_tpu/nope.py` |\n"
        )
        ctx2 = make_tree(tmp_path, {"docs/design.md": doc})
        # Same package, doctored docs: copy the package reference files in.
        import shutil
        shutil.copytree(
            REPO / "infinistore_tpu", tmp_path / "infinistore_tpu",
            ignore=shutil.ignore_patterns("__pycache__", "_native", "*.so"),
        )
        found = races.check_r005(ctx2, races.PackageIndex(ctx2))
        assert any(
            f.rule == "ITS-R005" and "stale" in f.key and "GhostClass" in f.key
            for f in found
        )
        del ctx

    # -- framework plumbing ---------------------------------------------------

    def test_requires_contract_is_honored(self, tmp_path):
        """`# its: requires[lock]` marks a caller-holds contract: the
        method's accesses count as guarded."""
        ctx = make_tree(tmp_path, {"infinistore_tpu/m.py": (
            "import threading\n\n\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        # its: guard[state: _lock]\n"
            "        self.state = 0\n"
            "        self._thread = None\n\n"
            "    def start(self):\n"
            "        self._thread = threading.Thread(target=self._run)\n\n"
            "    def _run(self):\n"
            "        with self._lock:\n"
            "            self._step()\n\n"
            "    def _step(self):  # its: requires[_lock]\n"
            "        self.state += 1\n\n"
            "    def read(self):\n"
            "        with self._lock:\n"
            "            return self.state\n"
        )})
        found = races.scan(ctx, docs=False)
        assert not [f for f in found if f.rule == "ITS-R001"]

    def test_inline_allow_suppresses_races_findings(self, tmp_path):
        ctx = mutated_pkg(
            tmp_path, "infinistore_tpu/tiering.py",
            sub=(
                "                while not self._dirty and not self._stop:\n"
                "                    if not self._cv.wait(timeout=self.interval_s):\n"
                "                        break",
                "                if not self._dirty and not self._stop:\n"
                "                    self._cv.wait(timeout=self.interval_s)"
                "  # its: allow[ITS-R004]",
            ),
        )
        found = races.scan(ctx, docs=False)
        hits = [f for f in found if f.rule == "ITS-R004"]
        assert hits and ctx.suppressed(hits[0])


# ---------------------------------------------------------------------------
# policy ITS-P004: layer-streaming saves name their QoS class at the source
# ---------------------------------------------------------------------------

P004_FIXTURE = '''\
from .wire import PRIORITY_FOREGROUND
import wire


def ship_named(conn, prompt, layer, kv, ids):
    conn.stage_layer_save(prompt, layer, kv, ids,
                          priority=PRIORITY_FOREGROUND)
    conn.stage_layer_save(prompt, layer, kv, ids,
                          priority=wire.PRIORITY_BACKGROUND)


def ship_default(conn, prompt, layer, kv, ids):
    conn.stage_layer_save(prompt, layer, kv, ids)


def ship_opaque(conn, prompt, layer, kv, ids, prio):
    conn.stage_layer_save(prompt, layer, kv, ids, priority=prio)
'''


class TestPolicyP004:
    def scan(self, tmp_path, rel="pkg/disagg.py"):
        ctx = make_tree(tmp_path, {rel: P004_FIXTURE})
        return policy.scan(
            ctx, package_rel="pkg", p001_exempt=set(), p002_exempt=set(),
            p003_files=set(), p004_files={"pkg/disagg.py", "pkg/vllm_v1.py"},
        )

    def test_default_and_opaque_priority_fire(self, tmp_path):
        p4 = [f for f in self.scan(tmp_path) if f.rule == "ITS-P004"]
        # The inherited-default call AND the opaque-variable call fire;
        # ITS-P002's "any explicit kwarg" is not enough here.
        scopes = sorted(f.key.split(":")[2] for f in p4)
        assert scopes == ["ship_default", "ship_opaque"]

    def test_literal_class_names_pass(self, tmp_path):
        p4 = [f for f in self.scan(tmp_path) if f.rule == "ITS-P004"]
        assert not [f for f in p4 if "ship_named" in f.key]

    def test_scope_is_producer_files_only(self, tmp_path):
        # Connector-layer forwards (priority=priority) live outside the
        # producer files and must not fire.
        ctx = make_tree(tmp_path, {"pkg/connector.py": P004_FIXTURE})
        found = policy.scan(
            ctx, package_rel="pkg", p001_exempt=set(), p002_exempt=set(),
            p003_files=set(), p004_files={"pkg/disagg.py"},
        )
        assert not [f for f in found if f.rule == "ITS-P004"]

    def test_vllm_is_in_p004_scope(self, tmp_path):
        found = self.scan(tmp_path, rel="pkg/vllm_v1.py")
        assert [f for f in found if f.rule == "ITS-P004"]
        assert "infinistore_tpu/vllm_v1.py" in policy.P004_FILES
        assert "infinistore_tpu/disagg.py" in policy.P004_FILES

    def test_real_producers_name_their_class(self):
        ctx = core.Context(str(REPO))
        found = [f for f in policy.scan(ctx) if f.rule == "ITS-P004"]
        assert found == []


# ---------------------------------------------------------------------------
# counters ITS-C009: disaggregated-handoff vocabulary lockstep
# ---------------------------------------------------------------------------

C009_DISAGG = '''\
class DisaggCounters:
    def __init__(self):
        self._c = {"disagg_handoffs": 0, "disagg_wrong_bytes": 0}

    def status(self):
        c = self._c
        return {**c, "disagg_overlap_layers": 1, "disagg_watermark_stalls": 0}
'''

C009_MANAGE_OK = '''\
def _disagg_prometheus_lines(ds):
    return [
        f"a {ds['disagg_handoffs']}",
        f"b {ds['disagg_wrong_bytes']}",
        f"c {ds['disagg_overlap_layers']}",
        f"d {ds['disagg_watermark_stalls']}",
    ]

route = "/disagg"   # served from _disagg_status()
'''

C009_DOCS = (
    "| disagg_handoffs | disagg_wrong_bytes | disagg_overlap_layers | "
    "disagg_watermark_stalls |\n"
)


class TestCountersDisagg:
    def scan(self, tmp_path, manage_src=C009_MANAGE_OK,
             disagg_src=C009_DISAGG, docs=C009_DOCS):
        ctx = make_tree(tmp_path, {
            "manage.py": manage_src,
            "disagg.py": disagg_src,
            "docs/disaggregation.md": docs,
        })
        return counters._scan_disagg(
            ctx, "manage.py", disagg_rel="disagg.py",
            docs_rel="docs/disaggregation.md",
        )

    def test_complete_vocabulary_is_clean(self, tmp_path):
        assert self.scan(tmp_path) == []

    def test_unexported_status_key_fires(self, tmp_path):
        manage = C009_MANAGE_OK.replace(
            "        f\"c {ds['disagg_overlap_layers']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(
            f.rule == "ITS-C009" and f.key.endswith(":disagg_overlap_layers")
            for f in found
        )

    def test_unexported_init_ledger_key_fires(self, tmp_path):
        # Keys living only in the __init__ counter dict are vocabulary too.
        manage = C009_MANAGE_OK.replace(
            "        f\"a {ds['disagg_handoffs']}\",\n", "")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith(":disagg_handoffs") for f in found)

    def test_stale_exporter_key_fires(self, tmp_path):
        manage = C009_MANAGE_OK.replace("disagg_wrong_bytes",
                                        "disagg_gone_key")
        keys = {f.key for f in self.scan(tmp_path, manage_src=manage)}
        assert any(k.endswith("stale:disagg_gone_key") for k in keys)
        assert any(k.endswith(":disagg_wrong_bytes") for k in keys)

    def test_undocumented_disagg_key_fires(self, tmp_path):
        docs = C009_DOCS.replace("disagg_watermark_stalls", "")
        found = self.scan(tmp_path, docs=docs)
        assert any(
            f.key.endswith("undocumented:disagg_watermark_stalls")
            for f in found
        )

    def test_missing_disagg_route_fires(self, tmp_path):
        manage = C009_MANAGE_OK.replace('"/disagg"', '"/nope"').replace(
            "_disagg_status", "nothing")
        found = self.scan(tmp_path, manage_src=manage)
        assert any(f.key.endswith("disagg-route") for f in found)

    def test_real_disagg_vocabulary_is_clean(self):
        ctx = core.Context(str(REPO))
        found = [f for f in counters.scan(ctx) if f.rule == "ITS-C009"]
        assert found == []


# ---------------------------------------------------------------------------
# modelcheck (ITS-M*)
# ---------------------------------------------------------------------------

def mini_spec(name="mini", **overrides):
    """A one-state spec that explores cleanly (complete, invariant held) —
    the neutral carrier for targeting ONE seeded defect per test."""
    kw = dict(
        name=name, doc="test fixture", initial_states=lambda: [(0,)],
        actions=(), invariants=(("true", lambda s: True),),
    )
    kw.update(overrides)
    return mspecs.Spec(**kw)


def ring_variant(**replacements):
    """The real ring spec with named actions swapped for mutants."""
    acts = tuple(replacements.get(a.name, a) for a in ring_spec.ACTIONS)
    return dataclasses.replace(ring_spec.SPEC, actions=acts)


def schedule_from(finding):
    """Parse the serialized counterexample out of an ITS-M finding."""
    m = re.search(r"counterexample schedule (\[.*?\]) \(replay",
                  finding.message)
    assert m, finding.message
    sched = json.loads(m.group(1))
    assert sched and all(isinstance(step, str) for step in sched)
    return sched


class TestModelcheck:
    def test_real_tree_is_clean_with_full_exploration(self):
        """The acceptance gate: every shipped spec explores its complete
        bounded state space at HEAD with zero findings, and the per-spec
        stats rows (states/edges/ms) land in Context.stats for --json."""
        ctx = core.Context(str(REPO))
        assert modelcheck.scan(ctx) == []
        rows = ctx.stats["modelcheck"]["specs"]
        assert set(rows) == {
            "membership_merge", "durable_log", "ring_sq_cq", "qos_aging",
        }
        for row in rows.values():
            assert row["states"] > 0 and row["edges"] > 0
            assert row["complete"] is True
            assert row["violations"] == []
            assert isinstance(row["ms"], float)

    # -- ITS-M001: stale action list vs the real class ----------------------

    def test_stale_action_list_vs_real_class_fires(self, tmp_path):
        ctx = make_tree(tmp_path, {"pkg/fake.py": (
            "class Membership:\n"
            "    def poke_method(self):\n"
            "        pass\n"
            "    def extra(self):\n"
            "        pass\n"
        )})
        spec = mini_spec(actions=(
            mspecs.Action("poke", lambda s: False, lambda s: s),
            mspecs.Action("mystery@0", lambda s: False, lambda s: s),
        ))
        mirrors = {
            "kind": "py_class", "file": "pkg/fake.py", "cls": "Membership",
            "actions": {"poke": "poke_method", "stale": "vanished"},
            "exempt": {"gone": "was audited once"},
        }
        found = modelcheck.scan(ctx, specs=[(spec, mirrors)])
        # All four drift directions, and nothing else (the carrier spec
        # itself explores cleanly).
        assert {f.key for f in found} == {
            "ITS-M001:pkg/fake.py:mini:unmapped:mystery",
            "ITS-M001:pkg/fake.py:mini:stale-covered:vanished",
            "ITS-M001:pkg/fake.py:mini:stale-exempt:gone",
            "ITS-M001:pkg/fake.py:mini:unmodeled:extra",
        }

    def test_mirrored_class_vanishing_fires(self, tmp_path):
        ctx = make_tree(tmp_path, {"pkg/fake.py": "class Other:\n    pass\n"})
        mirrors = {"kind": "py_class", "file": "pkg/fake.py",
                   "cls": "Membership", "actions": {}, "exempt": {}}
        found = modelcheck.scan(ctx, specs=[(mini_spec(), mirrors)])
        assert any(f.key.endswith(":missing-class") for f in found)

    def test_cpp_surface_strips_comments(self, tmp_path):
        """Prose like "bg_cooldown_us (hysteresis ...)" in a header comment
        must not read as a surface name the model has to cover."""
        ctx = make_tree(tmp_path, {"h.h": (
            "// bg_ghost (prose about a knob)\n"
            "/* ring_phantom ( multi-line\n   prose */\n"
            "static inline void bg_real(int x);\n"
        )})
        pattern = r"\b(bg_[a-z_]+|ring_[a-z_]+)\s*\("
        assert modelcheck._cpp_surface(ctx, "h.h", pattern) == {"bg_real"}

    # -- seeded protocol defects: the mutations MUST be caught ---------------

    def test_dropped_dekker_recheck_is_caught(self):
        """Mutate the ring model so the server parks WITHOUT the Dekker
        tail re-check (sleep straight after flag-set). Exploration must
        refute it — this is the lost-wakeup bug the discipline exists to
        prevent — and the finding must carry a replayable schedule."""
        sleepy = mspecs.Action(
            name="s_park_recheck",
            guard=lambda s: s[ring_spec.PC_S] == ring_spec.PARKING,
            apply=lambda s: ring_spec._set(
                s, s_parked=True, pc_s=ring_spec.IDLE),
        )
        spec = ring_variant(s_park_recheck=sleepy)
        ctx = core.Context(str(REPO))
        found = modelcheck.scan(ctx, specs=[(spec, ring_spec.MIRRORS)])
        assert found
        # Exploration findings only: the mutant's action names still match
        # the real ring.h surface, so M001 stays quiet.
        assert {f.rule for f in found} <= {"ITS-M002", "ITS-M003"}
        sched = schedule_from(found[0])
        assert any(step.startswith("s_park") for step in sched)

    def test_nonsticky_doorbell_strands_the_parker(self):
        """Drop the doorbell's socket-frame stickiness (and the re-check's
        insta-wake drain): a stale doorbell for an already-consumed publish
        takes the freshly-set park flag before the consumer sleeps, and the
        consumer then parks with its flag down — undoorbellable. The
        parked-flag-consistent invariant must find that exact schedule."""
        forgetful = mspecs.Action(
            name="p_doorbell",
            guard=lambda s: s[ring_spec.PC_P] == ring_spec.PUBLISHED,
            apply=lambda s: ring_spec._set(
                s, pc_p=ring_spec.IDLE,
                **({"sq_flag": 0, "s_parked": False}
                   if s[ring_spec.SQ_FLAG] else {}),
            ),
        )
        amnesiac = mspecs.Action(
            name="s_park_recheck",
            guard=lambda s: s[ring_spec.PC_S] == ring_spec.PARKING,
            apply=lambda s: (
                ring_spec._set(s, sq_flag=0, pc_s=ring_spec.IDLE)
                if s[ring_spec.SQ_TAIL] > s[ring_spec.SQ_HEAD]
                else ring_spec._set(s, s_parked=True, pc_s=ring_spec.IDLE)
            ),
        )
        spec = ring_variant(p_doorbell=forgetful, s_park_recheck=amnesiac)
        res = mspecs.explore(spec)
        bad = [v for v in res.violations
               if v.prop == "parked-flag-consistent"]
        assert bad
        # The shortest counterexample ends at the fatal sleep, with the
        # stale doorbell landing inside the park window.
        assert bad[0].schedule[-1] == "s_park_recheck"
        assert "p_doorbell" in bad[0].schedule

    def test_weakened_invariant_yields_replayable_counterexample(self):
        """Swap the membership no-resurrection step invariant for a
        WRONG/over-strict variant that also rejects the legal within-
        incarnation DEAD -> REMOVED terminal rank advance. Exploration
        must produce an ITS-M002 finding whose schedule ends in the
        offending exchange — the counterexample-to-test workflow's input
        (tests/test_modelcheck.py replays exactly this class of schedule
        against the real Membership)."""
        def too_strict(prev, action, nxt):
            if not action.startswith("exchange"):
                return True
            for i in range(membership_spec.N_PEERS):
                a = membership_spec._entry(prev, i)
                b = membership_spec._entry(nxt, i)
                if a == b:
                    continue
                if not membership_spec.beats(a, b):
                    return False
                if (a is not None and a[0] in membership_spec.TERMINAL
                        and b[1] <= a[1]):
                    return False  # no terminal-to-terminal carve-out
            return True

        spec = dataclasses.replace(
            membership_spec.SPEC,
            step_invariants=(
                ("no-resurrection", too_strict),
                ("epoch-monotone", membership_spec.step_epoch_monotone),
            ),
        )
        ctx = core.Context(str(REPO))
        found = modelcheck.scan(
            ctx, specs=[(spec, membership_spec.MIRRORS)])
        rows = [f for f in found
                if f.key == "ITS-M002:membership_merge:no-resurrection"]
        assert rows
        sched = schedule_from(rows[0])
        assert sched[-1].startswith("exchange@")
        # With violations present, the incomplete exploration is NOT
        # additionally reported as an M005 health finding.
        assert not any(f.rule == "ITS-M005" for f in found)

    # -- ITS-M005: exploration health ----------------------------------------

    def test_exploration_health_rules_fire(self, tmp_path):
        ctx = make_tree(tmp_path, {"h.h": "void zz_x(int);\n"})
        mirrors = {"kind": "cpp_functions", "file": "h.h",
                   "pattern": r"\b(zz_[a-z_]+)\s*\(",
                   "actions": {}, "exempt": {"zz_x": "fixture"}}
        runaway = mini_spec(
            name="runaway",
            actions=(mspecs.Action("inc", lambda s: True,
                                   lambda s: (s[0] + 1,)),),
            state_cap=8,
        )
        keys = {f.key for f in modelcheck.scan(ctx, specs=[
            (mini_spec(name="hollow", initial_states=lambda: []), mirrors),
            (mini_spec(name="blind", invariants=()), mirrors),
            (runaway, mirrors),
        ])}
        assert "ITS-M005:hollow:empty" in keys
        assert "ITS-M005:blind:no-invariants" in keys
        assert "ITS-M005:runaway:incomplete" in keys
