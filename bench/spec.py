"""What a cell is: its entry in BENCHMARK.json, its configuration file and
its traffic file, all found by name."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench_json: Path,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``bench_json``; its configuration file is the
    one BENCHMARK.json names, its traffic file
    ``<bench_dir>/traffic/<traffic>.json``."""
    bench_dir = bench_dir or BENCH_DIR
    spec = json.loads(Path(bench_json).read_text())
    cells: Dict[str, dict] = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    root = Path(bench_json).resolve().parent
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)
