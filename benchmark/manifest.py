"""``BENCHMARK.json`` written from the files under ``benchmark/``.

    python3 benchmark/manifest.py            # writes BENCHMARK.json
    python3 benchmark/manifest.py --check    # exit 1 where it differs

Every cell is a file under ``workloads/``, every metric one under
``metrics/`` and every configuration one under ``configs/``; a cell's file
lists the metrics it reports, and nothing else holds that list.  So a PR
that adds a cell adds its files and runs this; ``benchmark/tests`` holds the
committed ``BENCHMARK.json`` to what this builds.  ``manifest.json`` has what
belongs to no one file: the command, the paths and ``run_seconds``.
"""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def _files(kind: str) -> list:
    return [json.loads(p.read_text())
            for p in sorted((BENCH / kind).glob("*.json"))]


def build() -> dict:
    cells = _files("workloads")
    used = {c["config"] for c in cells}
    out = dict(json.loads((BENCH / "manifest.json").read_text()))
    out["configs"] = [
        {"name": c["name"], "source": c["source"],
         "file": f"benchmark/configs/{c['name']}.json",
         "reduced": list(c["reduced"]), "why": c["why"]}
        for c in _files("configs") if c["name"] in used]
    out["workloads"] = [{k: c[k] for k in ("name", "config", "traffic",
                                           "chips", "why")} for c in cells]
    for kind in ("end_to_end", "per_layer"):
        out[kind] = []
        for m in _files("metrics"):
            if m["kind"] != kind:
                continue
            where = [c["name"] for c in cells if m["name"] in c[kind]]
            if not where:
                raise ValueError(f"no cell reports {m['name']}")
            keys = ("name", "unit", "better", "bound", "source") \
                if kind == "end_to_end" \
                else ("name", "unit", "better", "source", "layer", "moves")
            entry = {k: m[k] for k in keys}
            if kind == "per_layer" or len(where) < len(cells):
                entry["workloads"] = where
            out[kind].append(entry)
    return out


def main(argv: list) -> int:
    text = json.dumps(build(), indent=2) + "\n"
    path = BENCH.parent / "BENCHMARK.json"
    if "--check" in argv:
        same = path.exists() and path.read_text() == text
        print("BENCHMARK.json", "is" if same else "is NOT",
              "what benchmark/ builds")
        return 0 if same else 1
    path.write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
