"""hypha-lint's own regression suite (tier-1).

Three layers: (1) every rule family catches its seeded violations in
tests/fixtures/lint/, (2) the suppression syntax and budget accounting
work, (3) the real package is lint-clean — the acceptance invariant
``python -m hypha_tpu.analysis hypha_tpu/`` exits 0, run in-process.
"""

from __future__ import annotations

import dataclasses
import json
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

from hypha_tpu.analysis import (
    DEFAULT_SUPPRESSION_BUDGET,
    RULES,
    lint_paths,
    lint_source,
    parse_sources,
)
from hypha_tpu.analysis.core import FileSource
from hypha_tpu.analysis import proto_rules

FIXTURES = Path(__file__).parent / "fixtures" / "lint"
REPO = Path(__file__).parent.parent
PACKAGE = REPO / "hypha_tpu"


def _rules_by_count(path: Path) -> Counter:
    report = lint_paths([path], protocol_checks=False)
    assert not report.parse_errors, report.parse_errors
    return Counter(v.rule for v in report.active)


# ---------------------------------------------------------------- fixtures


def test_async_fixture_catches_each_rule():
    counts = _rules_by_count(FIXTURES / "async_bad.py")
    assert counts["async-blocking-call"] == 3  # sleep, subprocess.run, open
    assert counts["task-black-hole"] == 2  # create_task + ensure_future
    assert counts["swallowed-cancel"] == 3  # bare, BaseException, tuple
    assert counts["lock-held-await"] == 1


def test_span_fixture_catches_rule():
    counts = _rules_by_count(FIXTURES / "span_bad.py")
    # bare call, assigned-then-entered, module helper — with-blocks,
    # begin/finish pairs and non-tracing .span receivers stay quiet.
    assert counts["span-not-scoped"] == 3
    assert set(counts) == {"span-not-scoped"}


@pytest.mark.parametrize("fixture", ["async_bad.py", "jax_bad.py", "span_bad.py"])
def test_fixture_clean_twins_stay_clean(fixture):
    """No violation may land inside a function whose name ends _is_fine."""
    path = FIXTURES / fixture
    lines = path.read_text().splitlines()
    report = lint_paths([path], protocol_checks=False)
    for v in report.active:
        enclosing = ""
        for line in reversed(lines[: v.line]):
            stripped = line.strip()
            if stripped.startswith(("def ", "async def ")):
                enclosing = stripped.split("def ", 1)[1].split("(", 1)[0]
                break
        assert not enclosing.endswith("_is_fine"), (v.rule, v.line, enclosing)


def test_naked_push_fixture_catches_rule():
    counts = _rules_by_count(FIXTURES / "naked_push.py")
    assert counts["naked-stream-push"] == 2  # self.node.push + node.push
    assert counts.total() == 2  # twins (lambda, *_once body, queue) clean


def test_naked_push_clean_twins_stay_clean():
    path = FIXTURES / "naked_push.py"
    lines = path.read_text().splitlines()
    report = lint_paths([path], protocol_checks=False)
    for v in report.active:
        enclosing = ""
        for line in reversed(lines[: v.line]):
            stripped = line.strip()
            if stripped.startswith(("def ", "async def ")):
                enclosing = stripped.split("def ", 1)[1].split("(", 1)[0]
                break
        assert not enclosing.endswith("_is_fine"), (v.rule, v.line, enclosing)


def test_jax_fixture_catches_each_rule():
    counts = _rules_by_count(FIXTURES / "jax_bad.py")
    assert counts["jit-host-sync"] == 3  # float(), .item(), np.asarray
    assert counts["jit-side-effect"] == 1
    assert counts["donated-buffer-reuse"] == 2  # decorator + wrapper forms


def test_suppression_waives_only_the_named_rule():
    report = lint_paths([FIXTURES / "suppressed.py"], protocol_checks=False)
    assert len(report.suppressed) == 2  # named waiver + disable=all
    # The waiver naming the wrong rule leaves its violation active AND is
    # itself flagged as a stale marker.
    assert sorted(v.rule for v in report.active) == [
        "async-blocking-call",
        "unused-suppression",
    ]
    assert len(report.suppression_sites) == 3


def test_suppression_budget_counts_comment_sites():
    report = lint_paths([FIXTURES / "suppressed.py"], protocol_checks=False)
    report.violations = [v for v in report.violations if v.suppressed]
    assert len(report.suppression_sites) == 3
    assert report.ok(budget=3)
    assert not report.ok(budget=2)  # budget exceeded == failure


def test_unused_suppression_flagged_and_marker_in_string_ignored():
    src = (
        "import time\n"
        "x = 1  # hypha-lint: disable=async-blocking-call\n"
        's = "suppress with # hypha-lint: disable=swallowed-cancel"\n'
    )
    report = lint_source("x.py", src)
    assert [v.rule for v in report.active] == ["unused-suppression"]
    assert report.active[0].line == 2  # the string literal is NOT a marker
    assert len(report.suppression_sites) == 1


def test_missing_path_is_an_error_not_a_green():
    report = lint_paths(["no/such/dir"], protocol_checks=False)
    assert report.parse_errors and not report.ok()


def test_undecodable_file_is_a_parse_error_not_a_crash(tmp_path):
    bad = tmp_path / "latin.py"
    bad.write_bytes(b"# -*- coding: latin-1 -*-\ns = '\xe9'\n")
    nul = tmp_path / "nul.py"
    nul.write_bytes(b"x = 1\x00\n")
    utf = tmp_path / "ok.py"
    utf.write_text("x = 1\n")
    report = lint_paths([tmp_path], protocol_checks=False)
    # latin-1 decodes fine via its PEP 263 cookie; the null byte errors;
    # the walk continues past it either way.
    assert any("nul.py" in e for e in report.parse_errors)
    assert not any("ok.py" in e for e in report.parse_errors)


def test_rule_filter_does_not_misfire_unused_suppression():
    src = (
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)  # hypha-lint: disable=async-blocking-call\n"
    )
    report = lint_source("x.py", src, rules={"unused-suppression"})
    assert not report.active  # the marker IS used, just filtered from view


# ---------------------------------------------------- inline-source checks


def test_blocking_call_in_nested_sync_def_not_flagged():
    src = (
        "import time, asyncio\n"
        "async def outer():\n"
        "    def inner():\n"
        "        time.sleep(1)\n"
        "    await asyncio.to_thread(inner)\n"
    )
    assert not lint_source("x.py", src).active


def test_lock_from_enclosing_frame_not_held_in_nested_def():
    src = (
        "import asyncio\n"
        "async def outer(lock, node):\n"
        "    async with lock:\n"
        "        async def later():\n"
        "            await node.request('p', '/x', None)\n"
        "        return later\n"
    )
    assert not lint_source("x.py", src).active


def test_parse_error_reported_not_raised():
    report = lint_source("bad.py", "def broken(:\n")
    assert report.parse_errors and not report.ok()


def test_every_rule_documented():
    fixture_rules = set()
    for f in (FIXTURES / "async_bad.py", FIXTURES / "jax_bad.py"):
        fixture_rules |= set(_rules_by_count(f))
    for rule in fixture_rules:
        assert rule in RULES
    dev_doc = (REPO / "docs" / "development.md").read_text()
    for rule in RULES:
        assert rule in dev_doc, f"rule {rule} missing from docs/development.md"


# ------------------------------------------- whole-program fixture packages


def _package_counts(pkg: str) -> Counter:
    report = lint_paths([FIXTURES / pkg], protocol_checks=False)
    assert not report.parse_errors, report.parse_errors
    return Counter(v.rule for v in report.active)


def test_conformance_package_exact_counts():
    counts = _package_counts("conformance_pkg")
    assert counts["proto-no-sender"] == 2  # OrphanMsg, GhostMsg
    assert counts["proto-no-handler"] == 2  # OrphanMsg, SilentMsg
    assert counts["round-tag-not-live"] == 2  # literal + constant-only local
    assert counts.total() == 6


def test_guard_package_flags_seeded_handler_only():
    counts = _package_counts("guard_pkg")
    assert counts == {"handler-mutates-before-guard": 1}


def test_flow_package_exact_counts():
    counts = _package_counts("flow_pkg")
    assert counts["async-blocking-reach"] == 1  # cleanup -> scrub -> rmtree
    assert counts["lock-held-await-reach"] == 1
    assert counts.total() == 2


def test_leak_package_exact_counts():
    # Direct acquire in the task body + one more a call-hop down.
    counts = _package_counts("leak_pkg")
    assert counts == {"task-resource-leak": 2}


@pytest.mark.parametrize(
    "pkg", ["conformance_pkg", "guard_pkg", "flow_pkg", "leak_pkg"]
)
def test_package_clean_twins_stay_clean(pkg):
    """No whole-program violation may land inside a *_is_fine function."""
    report = lint_paths([FIXTURES / pkg], protocol_checks=False)
    for v in report.active:
        lines = Path(v.path).read_text().splitlines()
        enclosing = ""
        for line in reversed(lines[: v.line]):
            stripped = line.strip()
            if stripped.startswith(("def ", "async def ")):
                enclosing = stripped.split("def ", 1)[1].split("(", 1)[0]
                break
        assert not enclosing.endswith("_is_fine"), (v.rule, v.path, v.line)


def test_explicit_stale_waiver_fails_loudly():
    from hypha_tpu.analysis import graph, handler_rules

    errors: list[str] = []
    sources = parse_sources([FIXTURES / "guard_pkg"], errors)
    assert not errors
    project = graph.build_project(sources, [FIXTURES / "guard_pkg"])
    bad = handler_rules.check(project, waivers={"NeverDeclared": "why"})
    assert any(v.rule == "proto-unused-waiver" for v in bad)
    # ... but the GLOBAL waiver table is only judged against the canonical
    # tree: a fixture package declaring none of its names says nothing.
    assert not any(
        v.rule == "proto-unused-waiver" for v in handler_rules.check(project)
    )


def test_changed_only_scopes_file_local_but_not_whole_program():
    pkg = FIXTURES / "guard_pkg"
    handlers = (pkg / "handlers.py").resolve()
    report = lint_paths(
        [FIXTURES / "async_bad.py", pkg],
        protocol_checks=False,
        changed_only={str(handlers)},
    )
    counts = Counter(v.rule for v in report.active)
    # The whole-program pass still sees every parsed file...
    assert counts["handler-mutates-before-guard"] == 1
    # ...while file-local findings in the out-of-scope file are dropped.
    assert counts["async-blocking-call"] == 0
    assert counts["swallowed-cancel"] == 0


# -------------------------------------------------------- protocol family


def test_proto_roundtrip_catches_seeded_bad_class():
    @dataclasses.dataclass
    class Broken:
        values: set = dataclasses.field(default_factory=set)  # CBOR can't

    bad = proto_rules.check_roundtrip(registry={"Broken": Broken})
    assert [v.rule for v in bad] == ["msg-roundtrip"]


def test_proto_round_tag_catches_seeded_bad_class():
    @dataclasses.dataclass
    class Push:
        job_id: str = ""

    bad = proto_rules.check_round_tags(
        registry={"Push": Push}, required=frozenset({"Push"})
    )
    assert [v.rule for v in bad] == ["msg-missing-round-tag"]


def test_proto_round_tag_catches_renamed_required_class():
    bad = proto_rules.check_round_tags(
        registry={}, required=frozenset({"RenamedAway"})
    )
    assert [v.rule for v in bad] == ["msg-missing-round-tag"]
    assert "REQUIRES_ROUND_TAG" in bad[0].message


def test_proto_fragment_rule_on_fixture_pair():
    """The seeded fixture pair: FragBad (fragment_id, no round) fires the
    rule, clean twin FragGood stays quiet. The fixtures are deliberately
    unregistered — they reach the rule as an explicit registry."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "proto_fragment", FIXTURES / "proto_fragment.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = proto_rules.check_fragment_tags(
        registry={"FragBad": mod.FragBad, "FragGood": mod.FragGood}
    )
    assert [v.rule for v in bad] == ["msg-fragment-needs-round"]
    assert "FragBad" in bad[0].message
    assert proto_rules.check_fragment_tags(
        registry={"FragGood": mod.FragGood}
    ) == []


def test_proto_fragment_rule_accepts_epoch_as_round_tag():
    @dataclasses.dataclass
    class EpochTagged:
        epoch: int = 0
        fragment_id: int = 0

    assert proto_rules.check_fragment_tags(
        registry={"EpochTagged": EpochTagged}
    ) == []


def test_proto_fragment_rule_live_registry_clean():
    """The shipping registry (FragmentTag et al.) satisfies the rule."""
    assert proto_rules.check_fragment_tags() == []


def test_proto_shard_rule_on_fixture_pair():
    """The seeded fixture pair: ShardBad (shard identity, no round) fires
    the rule, clean twin ShardGood stays quiet. The fixtures are
    deliberately unregistered — they reach the rule as an explicit
    registry."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "proto_shard", FIXTURES / "proto_shard.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = proto_rules.check_shard_tags(
        registry={"ShardBad": mod.ShardBad, "ShardGood": mod.ShardGood}
    )
    assert [v.rule for v in bad] == ["msg-shard-needs-round"]
    assert "ShardBad" in bad[0].message
    assert proto_rules.check_shard_tags(
        registry={"ShardGood": mod.ShardGood}
    ) == []


def test_proto_shard_rule_ignores_config_counts():
    """shard_index/num_ps_shards are config COUNTS, not wire identities —
    the per-push identity travels as the SHARD_KEY header next to round
    (messages.AggregateExecutorConfig's documented contract)."""

    @dataclasses.dataclass
    class ConfigLike:
        shard_index: int = 0
        num_ps_shards: int = 1

    assert proto_rules.check_shard_tags(registry={"ConfigLike": ConfigLike}) == []


def test_proto_shard_rule_live_registry_clean():
    """The shipping registry (ShardMap, shard-stamped Progress) satisfies
    the rule."""
    assert proto_rules.check_shard_tags() == []


def test_proto_adaptive_rule_on_fixture_pair():
    """The seeded fixture pair: AdaptiveBad (per-peer inner_steps/codecs,
    no round tag) fires the rule, clean twin AdaptiveGood stays quiet. The
    fixtures are deliberately unregistered — they reach the rule as an
    explicit registry."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "proto_adaptive", FIXTURES / "proto_adaptive.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = proto_rules.check_adaptive_tags(
        registry={"AdaptiveBad": mod.AdaptiveBad, "AdaptiveGood": mod.AdaptiveGood}
    )
    assert [v.rule for v in bad] == ["msg-adaptive-needs-round"]
    assert "AdaptiveBad" in bad[0].message
    assert proto_rules.check_adaptive_tags(
        registry={"AdaptiveGood": mod.AdaptiveGood}
    ) == []


def test_proto_adaptive_rule_live_registry_clean():
    """The shipping registry (RoundMembership.inner_steps rides its epoch)
    satisfies the rule."""
    assert proto_rules.check_adaptive_tags() == []


def test_proto_generation_rule_on_fixture_pair():
    """The seeded fixture pair: GenerationBad (a restart-handshake
    generation, no round tag) fires the rule, clean twin GenerationGood
    stays quiet. Unregistered fixtures, explicit registry."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "proto_generation", FIXTURES / "proto_generation.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = proto_rules.check_generation_tags(
        registry={
            "GenerationBad": mod.GenerationBad,
            "GenerationGood": mod.GenerationGood,
        }
    )
    assert [v.rule for v in bad] == ["msg-generation-needs-round"]
    assert "GenerationBad" in bad[0].message
    assert proto_rules.check_generation_tags(
        registry={"GenerationGood": mod.GenerationGood}
    ) == []


def test_proto_generation_rule_live_registry_clean():
    """The shipping registry (SchedulerHello/AdoptAck carry round next to
    generation; ProgressResponse pairs generation with round) satisfies
    the rule at zero new suppressions."""
    assert proto_rules.check_generation_tags() == []


def test_proto_swap_rule_on_fixture_pair():
    """The seeded fixture pair: SwapBad (a weight_round stamp with no
    generation half) fires the rule, clean twin SwapGood (the full
    (round, generation) pair) stays quiet. Unregistered fixtures,
    explicit registry."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "proto_swap", FIXTURES / "proto_swap.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = proto_rules.check_swap_tags(
        registry={"SwapBad": mod.SwapBad, "SwapGood": mod.SwapGood}
    )
    assert [v.rule for v in bad] == ["msg-swap-needs-generation"]
    assert "SwapBad" in bad[0].message
    assert "generation" in bad[0].message
    assert proto_rules.check_swap_tags(
        registry={"SwapGood": mod.SwapGood}
    ) == []


def test_proto_swap_rule_live_registry_clean():
    """The shipping registry satisfies the rule at zero new suppressions:
    GenerateResponse and ServeLoad carry weight_round NEXT TO
    weight_generation (the live-weight-streaming stamp pair)."""
    assert proto_rules.check_swap_tags() == []


def test_proto_block_rule_on_fixture_pair():
    """The seeded fixture pair: BlockBad (chain hashes with no weight
    stamp) fires the rule, clean twin BlockGood (hashes next to the full
    (weight_round, weight_generation) pair) stays quiet. Unregistered
    fixtures, explicit registry."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "proto_block", FIXTURES / "proto_block.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = proto_rules.check_block_tags(
        registry={"BlockBad": mod.BlockBad, "BlockGood": mod.BlockGood}
    )
    assert [v.rule for v in bad] == ["msg-block-needs-generation"]
    assert "BlockBad" in bad[0].message
    assert "generation" in bad[0].message
    assert proto_rules.check_block_tags(
        registry={"BlockGood": mod.BlockGood}
    ) == []


def test_proto_block_rule_live_registry_clean():
    """The shipping registry satisfies the rule at zero new suppressions:
    the fleet-cache wire (BlockPull/BlockChain/MigrateRequest) carries
    chain hashes NEXT TO the (weight_round, weight_generation) stamp."""
    assert proto_rules.check_block_tags() == []


def test_proto_tree_rule_on_fixture_pair():
    """The seeded fixture pair: TreeBad (tree_depth/parent placement, no
    round tag) fires the rule, clean twin TreeGood stays quiet.
    Unregistered fixtures, explicit registry."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "proto_tree", FIXTURES / "proto_tree.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = proto_rules.check_tree_tags(
        registry={"TreeBad": mod.TreeBad, "TreeGood": mod.TreeGood}
    )
    assert [v.rule for v in bad] == ["msg-tree-needs-round"]
    assert "TreeBad" in bad[0].message
    assert proto_rules.check_tree_tags(
        registry={"TreeGood": mod.TreeGood}
    ) == []


def test_proto_tree_rule_live_registry_clean():
    """The shipping registry (ShardMap carries round next to tree_depth)
    satisfies the rule at zero new suppressions."""
    assert proto_rules.check_tree_tags() == []


def test_proto_manifest_catches_stale_value_vocabulary():
    bad = proto_rules.check_protocol_map(
        registry={}, manifest={}, values={"GhostValue"}
    )
    assert [v.rule for v in bad] == ["msg-unmapped-protocol"]
    assert "stale" in bad[0].message


def test_proto_manifest_catches_unclaimed_and_stale():
    @dataclasses.dataclass
    class Orphan:
        x: int = 0

    bad = proto_rules.check_protocol_map(
        registry={"Orphan": Orphan},
        manifest={"/p/1": ("Ghost",)},
        values=set(),
    )
    assert sorted(v.rule for v in bad) == [
        "msg-unmapped-protocol",
        "msg-unmapped-protocol",
    ]


def test_proto_manifest_catches_double_claimed_message():
    @dataclasses.dataclass
    class Dup:
        x: int = 0

    bad = proto_rules.check_protocol_map(
        registry={"Dup": Dup},
        manifest={"/p/1": ("Dup",), "/p/2": ("Dup",)},
        values=set(),
    )
    assert [v.rule for v in bad] == ["msg-double-claimed"]
    assert "/p/1" in bad[0].message and "/p/2" in bad[0].message


def test_proto_manifest_single_claim_stays_clean():
    @dataclasses.dataclass
    class Solo:
        x: int = 0

    assert (
        proto_rules.check_protocol_map(
            registry={"Solo": Solo}, manifest={"/p/1": ("Solo",)}, values=set()
        )
        == []
    )


def test_proto_suppression_matches_decorator_block_and_class_line():
    @dataclasses.dataclass  # hypha-lint: disable=msg-roundtrip
    class DecoratorWaived:
        x: int = 0

    @dataclasses.dataclass
    class ClassLineWaived:  # hypha-lint: disable=msg-roundtrip
        x: int = 0

    @dataclasses.dataclass
    class NotWaived:
        x: int = 0

    assert proto_rules._suppressed_on_def(DecoratorWaived, "msg-roundtrip")
    assert proto_rules._suppressed_on_def(ClassLineWaived, "msg-roundtrip")
    assert not proto_rules._suppressed_on_def(ClassLineWaived, "msg-missing-round-tag")
    assert not proto_rules._suppressed_on_def(NotWaived, "msg-roundtrip")


def test_sample_instance_covers_every_registered_message():
    from hypha_tpu import messages
    from hypha_tpu.ft import membership  # noqa: F401  (registers FT types)

    for name, cls in sorted(messages.wire_registry().items()):
        sample = proto_rules.sample_instance(cls)
        assert isinstance(sample, cls), name


# ------------------------------------------------------- the real package


def test_package_is_lint_clean():
    """The acceptance invariant, in-process: zero unsuppressed violations
    and the suppression budget holds over hypha_tpu/."""
    report = lint_paths([PACKAGE], protocol_checks=True)
    assert not report.parse_errors, report.parse_errors
    assert not report.active, "\n".join(v.render() for v in report.active)
    assert len(report.suppression_sites) <= DEFAULT_SUPPRESSION_BUDGET


def test_cli_exits_zero_on_package():
    proc = subprocess.run(
        [sys.executable, "-m", "hypha_tpu.analysis", str(PACKAGE)],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_exits_nonzero_on_fixture():
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "hypha_tpu.analysis",
            "--no-proto",
            str(FIXTURES / "async_bad.py"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 1
    assert "swallowed-cancel" in proc.stdout


def test_cli_rule_filter_and_listing():
    proc = subprocess.run(
        [sys.executable, "-m", "hypha_tpu.analysis", "--list-rules"],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0
    for rule in RULES:
        assert rule in proc.stdout
    only = subprocess.run(
        [
            sys.executable,
            "-m",
            "hypha_tpu.analysis",
            "--no-proto",
            "--rule",
            "task-black-hole",
            str(FIXTURES / "async_bad.py"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert only.returncode == 1
    assert "task-black-hole" in only.stdout
    assert "swallowed-cancel" not in only.stdout


def test_benchmarks_and_drivers_lint_clean():
    """The fix sweep stays fixed: the chip probes, the bring-up script, the
    tests' cluster harnesses and the verify drivers run the full pass
    (file-local + whole-program) at zero suppressions."""
    report = lint_paths(
        [
            REPO / "benchmarks",
            REPO / "chip_smoke.py",
            REPO / "tests" / "harness",
            REPO / ".claude" / "skills" / "verify",
        ],
        protocol_checks=False,
    )
    assert not report.parse_errors, report.parse_errors
    assert not report.active, "\n".join(v.render() for v in report.active)
    assert not report.suppression_sites


def test_cli_json_format_on_fixture_package():
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "hypha_tpu.analysis",
            "--no-proto",
            "--format",
            "json",
            str(FIXTURES / "conformance_pkg"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["ok"] is False
    assert {"rule", "path", "line", "message", "suppressed"} <= set(
        payload["violations"][0]
    )
    assert payload["suppressions"]["used"] == 0
    cov = payload["protocol_coverage"]["/demo/0.0.1"]
    assert cov["PingMsg"]["covered"] is True
    assert cov["ReplyMsg"]["covered"] is True  # reply position + .request
    assert cov["OrphanMsg"]["covered"] is False


def test_cli_json_package_every_message_covered_or_waived():
    """The acceptance invariant for the coverage table: every live
    PROTOCOL_MESSAGES entry has sender+consumer evidence or a documented
    waiver."""
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "hypha_tpu.analysis",
            "--format",
            "json",
            str(PACKAGE),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    cov = payload["protocol_coverage"]
    assert len(cov) >= 9  # the live protocols plus the gossip topic
    for proto, row in sorted(cov.items()):
        assert row, proto
        for msg, ev in row.items():
            assert ev["covered"] or ev["waived"], (proto, msg, ev)


def test_cli_changed_bad_ref_falls_back_to_full_run():
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "hypha_tpu.analysis",
            "--no-proto",
            "--changed",
            "no-such-ref-hypha",
            str(FIXTURES / "async_bad.py"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 1
    assert "falling back" in proc.stderr
    assert "swallowed-cancel" in proc.stdout


def test_cli_dump_graph():
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "hypha_tpu.analysis",
            "--dump-graph",
            str(FIXTURES / "guard_pkg"),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=60,
    )
    assert proc.returncode == 0
    assert "guard_pkg.handlers:BadState.on_update" in proc.stdout
    assert "# protocol manifest" in proc.stdout
    assert "/guard/0.0.1: EpochUpdate" in proc.stdout


def test_file_source_suppression_parsing():
    src = FileSource(
        "s.py",
        "x = 1  # hypha-lint: disable=a, b\n"
        "y = 2  # hypha-lint: disable=all\n"
        "z = 3\n",
    )
    assert src.suppressed_at(1, "a") and src.suppressed_at(1, "b")
    assert not src.suppressed_at(1, "c")
    assert src.suppressed_at(2, "anything")
    assert not src.suppressed_at(3, "a")
