"""README.md and docs/ name only measuring scripts and records that exist:
the ones the chip round replaced were cited there long after nobody ran them."""

from __future__ import annotations

import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_readme_and_docs_cite_no_script_or_record_that_is_gone():
    gone = []
    for doc in [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))]:
        text = doc.read_text()
        cited = set(re.findall(r"\bbenchmarks/\w+\.py\b", text))
        cited |= set(re.findall(r"\b[A-Z][A-Z0-9]*_r\d\d(?:\.telemetry)?\.json\b", text))
        gone += [f"{doc.relative_to(REPO)}: {path}" for path in sorted(cited)
                 if not (REPO / path).exists()]
    assert not gone, gone
