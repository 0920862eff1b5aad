"""Tiny sizes of every cell, for the benchmark's CPU tests.

Each cell's tiny size is a file of its own, ``tests/bench/tiny/<cell>.json``:
``{"config": {...}, "traffic": {...}}``, entries that replace the
configuration's and the traffic mix's to shrink the cell.  A cell that
`BENCHMARK.json` does not hold yet also carries its benchmark entries
under ``"entries"`` (``workload``, ``end_to_end``, ``per_layer``), and
the tests add them to a copy.  A new cell is a new file here: nothing
below needs an edit.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_DIR = Path(__file__).resolve().parent / "tiny"


def load_tiny(directory: Path = TINY_DIR) -> dict:
    """cell name -> its tiny file's contents, for every file in `directory`."""
    return {p.stem: json.loads(p.read_text()) for p in sorted(directory.glob("*.json"))}


TINY = load_tiny()


def with_unlisted(bench: dict, tiny: dict = TINY) -> dict:
    """BENCHMARK.json's entries with those of every tiny cell it lacks."""
    bench = json.loads(json.dumps(bench))
    have = {w["name"] for w in bench["workloads"]}
    for name, t in sorted(tiny.items()):
        entries = t.get("entries")
        if entries is None or name in have:
            continue
        bench["workloads"].append(entries["workload"])
        bench["end_to_end"].extend(entries["end_to_end"])
        bench["per_layer"].extend(entries["per_layer"])
    return bench


def root_with_unlisted(tmp_path):
    """A checkout root whose BENCHMARK.json also holds the unlisted cells."""
    bench = with_unlisted(json.loads((ROOT / "BENCHMARK.json").read_text()))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path
