"""Docs-freshness gate: every file path, dotted `repro.*` name, and CLI
flag mentioned in README.md / docs/ARCHITECTURE.md must exist, import,
or parse — stale docs fail CI instead of rotting silently."""

import importlib
import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOCS = (REPO / "README.md", REPO / "docs" / "ARCHITECTURE.md")


def _text() -> str:
    return "\n".join(p.read_text() for p in DOCS)


def test_doc_files_exist():
    for p in DOCS:
        assert p.is_file(), f"{p} is missing"
        assert p.stat().st_size > 0


def test_referenced_paths_exist():
    """`repro/...`, `src/...`, `tests/...`, `benchmarks/...`, `docs/...`
    paths named in the docs must exist on disk (bare `repro/` maps under
    `src/`; directory references may omit a trailing slash)."""
    pat = re.compile(  # lookbehind skips URL segments like .../repro/...
        r"(?<![\w/.-])((?:src/|tests/|benchmarks/|docs/|repro/)[\w/.-]*[\w/])")
    missing = []
    for ref in sorted(set(pat.findall(_text()))):
        rel = "src/" + ref if ref.startswith("repro/") else ref
        p = REPO / rel
        if not (p.exists() or p.parent.joinpath(p.name + ".py").exists()):
            missing.append(ref)
    assert not missing, f"docs reference nonexistent paths: {missing}"


def test_dotted_module_references_resolve():
    """Every `repro.x.y[.attr...]` mention must import as a module (the
    longest importable prefix) and resolve the remainder via getattr."""
    pat = re.compile(r"\brepro(?:\.[A-Za-z_]\w*)+")
    bad = []
    for name in sorted(set(pat.findall(_text()))):
        parts = name.split(".")
        obj, rest = None, None
        for k in range(len(parts), 0, -1):
            try:
                obj = importlib.import_module(".".join(parts[:k]))
                rest = parts[k:]
                break
            except ImportError:
                continue
        if obj is None:
            bad.append(name)
            continue
        try:
            for attr in rest:
                obj = getattr(obj, attr)
        except AttributeError:
            bad.append(name)
    assert not bad, f"docs reference unresolvable names: {bad}"


def test_cli_flags_exist():
    """Every `--flag` the docs mention must be a real option of
    repro.launch.train's, repro.launch.serve's or chip_smoke.py's
    parser."""
    import importlib.util

    from repro.launch.serve import build_parser as serve_parser
    from repro.launch.train import build_parser as train_parser
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    known = set()
    for parser in (train_parser(), serve_parser(), chip_smoke._parser()):
        for act in parser._actions:
            known.update(act.option_strings)
    flags = set(re.findall(r"(?<![\w-])--[a-z][a-z0-9-]*", _text()))
    unknown = sorted(flags - known)
    assert not unknown, f"docs mention unknown CLI flags: {unknown}"


def test_documented_co_invocation_parses():
    """The co-controller example command in README/ARCHITECTURE parses
    to the documented values."""
    from repro.launch.train import build_parser
    args = build_parser().parse_args([
        "--arch", "gpt2-small", "--controller", "co",
        "--rank-buckets", "2,4,8",
        "--compressor-buckets", "none,int8,topk", "--straggler-sim"])
    assert args.controller == "co"
    assert args.rank_buckets == (2, 4, 8)
    assert args.compressor_buckets == ("none", "int8", "topk")
    assert args.straggler_sim


def test_knob_table_matches_config():
    """The README knob table's config names must be real SystemConfig
    fields, and live there only: a run setting has one home."""
    import dataclasses

    from repro.config.base import SplitConfig
    from repro.core.system import SystemConfig
    sys_fields = {f.name for f in dataclasses.fields(SystemConfig)}
    split_fields = {f.name for f in dataclasses.fields(SplitConfig)}
    for knob in ("controller", "rank_buckets", "compressor_buckets",
                 "acc_dead_band", "min_gain", "continuous_topk"):
        assert knob in sys_fields, f"SystemConfig.{knob} missing"
        assert knob not in split_fields, f"SplitConfig.{knob} duplicates it"
