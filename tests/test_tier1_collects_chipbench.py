"""``pytest tests/`` runs the benchmark's own tests: every
``chipbench/tests/test_<x>.py`` has a ``tests/test_chipbench_<x>.py`` that
imports its cases, so a cell's tests count from the day they are written."""

from pathlib import Path

REPO = Path(__file__).parent.parent


def test_every_chipbench_test_file_is_collected_by_tier1():
    theirs = sorted(p.stem for p in (REPO / "chipbench/tests").glob("test_*.py"))
    assert theirs
    for stem in theirs:
        ours = REPO / "tests" / f"test_chipbench_{stem[len('test_'):]}.py"
        assert ours.is_file(), f"{ours.name} is missing: chipbench/tests/" \
            f"{stem}.py is not run by tier-1"
        assert f"from chipbench.tests.{stem} import *" in ours.read_text()
