"""The benchmark's own tests run on the CPU and are not part of tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider
"""
import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def toy_root(tmp_path):
    """A checkout in little: the real ``BENCHMARK.json`` with its
    configurations replaced by the toy and every cell renamed
    ``toy.<traffic>``, the toy's traffic files in the real ones' place.
    The metric files and readers are the real ones."""
    from benchmarks.harness import spec

    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    base = tmp_path / bench["paths"][0]
    shutil.copytree(os.path.join(HERE, "toy", "traffic"), base / "traffic")
    shutil.copy(os.path.join(HERE, "toy", "config.json"), base / "toy.json")
    rename = {w["name"]: "toy." + w["traffic"] for w in bench["workloads"]}
    bench["configs"] = [{"name": "toy",
                         "file": f"{bench['paths'][0]}/toy.json"}]
    bench["workloads"] = [
        {"name": n, "config": "toy", "traffic": n.split(".", 1)[1],
         "chips": 1} for n in sorted(set(rename.values()))]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({rename[w] for w in m["workloads"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)
