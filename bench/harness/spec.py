"""Find a cell, its configuration, its traffic mix, its engine and its
metrics by name.

``BENCHMARK.json`` at the root of the checkout names every cell; each
cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/workloads/<traffic>.json``); the mix names the engine
that drives it (``bench/harness/<engine>.py``, class ``Engine``), and each
metric is a reader ``bench/metrics/<metric>.py``. Nothing here knows any
particular cell: adding one means adding files and entries, never
editing code.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/workloads/<traffic>.json
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _applies(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a per-layer metric without a list goes with every cell that
    # reports the end-to-end metric it moves
    return metric.get("moves", metric["name"]) in e2e_of_cell


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "workloads" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def load_reader(metric: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_engine(name: str):
    """The ``Engine`` class of ``bench/harness/<name>.py``."""
    return importlib.import_module(f"harness.{name}").Engine


def use_checkout_cache(root: Path = ROOT) -> str:
    """Turn on JAX's persistent compilation cache, in
    ``<checkout>/.jax_cache``, through the program's own helper; returns
    the directory. Call it before JAX is first imported: JAX reads the
    variable then. The checkout's directory replaces any the machine
    sets, so that two checkouts on one machine share no cache."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    return enable_compile_cache()
