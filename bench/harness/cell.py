"""A cell, found by name: its entry in BENCHMARK.json, its configuration
file, its traffic mix, its query encoder and the readers of its per-layer
metrics.

Everything that belongs to one configuration, one traffic mix, one encoder
architecture or one per-layer metric sits in a file of its own
(``configs/<config>.json``, ``traffic/<mix>.json``,
``harness/encoders/<arch>.py``, ``metrics/<metric>.py``), so a cell is
added by adding files and an entry, never by editing one.

An encoder module, named by ``"arch"`` in the configuration's ``encoder``
group, provides:

* ``widths(published) -> dict``: the widths under the module's own names;
* ``embed_dim(w)``, ``seq_len(w)``: the width of a query embedding and the
  fixed length of a query, in tokens;
* ``make_params(keys, w)``: the seeded encoder tree from a stack of eleven
  keys, traced inside the one jitted maker of ``weights.make_weights``;
* ``service_encoder(w, seq_len, name)``: what ``RouterService`` is handed;
* ``reference(params, tokens, mode)``: plain ``jax.numpy`` in float32,
  every contraction through ``reference.einsum`` at ``mode``;
* ``flops_per_query(w, seq_len)``: the model FLOPs of one query.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
ENCODERS = BENCH / "harness" / "encoders"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list        # metric entries this cell reports, --trace 0
    per_layer: list         # metric entries this cell reports, --trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    return Cell(name, int(w["chips"]), config, mix,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return _module(BENCH / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric.replace('.', '_')}").read


def encoder(published: dict):
    """The module of ``harness/encoders/<arch>.py`` that a configuration's
    ``encoder`` group names by ``arch``."""
    have = sorted(p.stem for p in ENCODERS.glob("*.py"))
    arch = published.get("arch")
    if arch not in have:
        raise ValueError(f"encoder arch {arch!r} is not one of the encoder "
                         f"modules in {ENCODERS}: {have}")
    return _encoder(ENCODERS / f"{arch}.py")


@functools.lru_cache(maxsize=None)
def _encoder(path: pathlib.Path):
    # one module object per file, so that the jitted maker and reference
    # are traced once per process
    return _module(path, f"bench_encoder_{path.stem}")
