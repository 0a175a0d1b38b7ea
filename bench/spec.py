"""What a cell is: its entry in BENCHMARK.json, its configuration file,
its traffic file and its admission reference, all found by name."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

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
    # ``mismatch(rec) -> int`` of ``bench/admission/<check.admission>.py``
    admission: Callable[[dict], int]


def load_part(folder: str, name: str, attr: str,
              bench_dir: Optional[Path] = None):
    """``attr`` of ``<bench_dir>/<folder>/<name>.py``, loaded by path.  An
    unknown name fails here, naming the files that exist."""
    bench_dir = bench_dir or BENCH_DIR
    path = bench_dir / folder / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in (bench_dir / folder).glob("*.py"))
        raise SystemExit(f"bench: no {folder}/{name}.py; known: {known}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench_json: Path,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``bench_json``; its configuration file is the
    one BENCHMARK.json names, its traffic file
    ``<bench_dir>/traffic/<traffic>.json``, its admission reference
    ``<bench_dir>/admission/<check.admission>.py``."""
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
    admission = load_part("admission", config["check"]["admission"],
                          "mismatch", bench_dir)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, admission=admission)
