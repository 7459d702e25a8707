"""``BENCHMARK.json`` and the data files it names, resolved for one cell.

A cell is a ``workloads`` entry: a configuration (``configs/<name>.json``,
or the ``file`` its manifest entry gives), a traffic mix
(``traffic/<name>.json``) and the metrics that apply to it, each per-layer
metric with its reader spec (``layer_metrics/<name>.json``). A later PR adds
a cell by adding files and entries; nothing here knows a cell by name.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# A traffic file's keys: the ones the harness reads, and the ones that only
# say where the numbers came from. Any other key would be read by nothing and
# promise a mix that is not run, so it is refused.
TRAFFIC_READ = {"inner_steps", "batch", "sequence", "data", "job_sets", "checks"}
TRAFFIC_NOTES = {"inner_steps_rule", "batch_ladder", "note"}


class ManifestError(Exception):
    pass


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # manifest entries reported by this cell
    per_layer: list  # (manifest entry, reader spec) reported by this cell


def _load(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise ManifestError(f"{path} does not exist") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from None


def load_manifest(root: Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def load_traffic(path: Path) -> dict:
    traffic = _load(path)
    unknown = sorted(set(traffic) - TRAFFIC_READ - TRAFFIC_NOTES)
    if unknown:
        raise ManifestError(
            f"{path} has keys that nothing reads: {unknown}. What the job is "
            "to do differently goes into job_sets, as scheduler --set strings"
        )
    return traffic


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(workload: str, root: Path = ROOT, manifest: dict | None = None) -> Cell:
    """The cell of that name. ``manifest`` stands in for ``BENCHMARK.json``
    where a cell that is not (or not yet) in it is wanted."""
    manifest = manifest or load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise ManifestError(
            f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})"
        )
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {workload!r} names no known config")
    bench = root / BENCH.name
    per_layer = [
        (m, _load(bench / "layer_metrics" / f"{m['name']}.json"))
        for m in manifest["per_layer"] if _applies(m, workload)
    ]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_load(root / configs[w["config"]]["file"]),
        traffic=load_traffic(bench / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer,
    )
