"""One cell's inputs and its sweep grid, found by name in data files.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, ``configs/<config>.json``, and a traffic mix,
``traffic/<traffic>.json``. The configuration fixes the network, its
weights (``weights/<model>.npz``), the mesh and the wire precision; the traffic mix
fixes the sweep axes (MC placements, packet->MC affinities, orderings)
and how the warm-up drains. Adding a cell adds files; nothing here names
a cell.
"""
from __future__ import annotations

import json
import os
from typing import List, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def workload(name: str) -> Tuple[dict, dict, dict]:
    """(cell entry, configuration, traffic mix) of the named cell."""
    cells = {w["name"]: w for w in manifest()["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    return cell, load_json("configs", cell["config"]), load_json(
        "traffic", cell["traffic"])


def mesh_name(config: dict) -> str:
    noc = config["noc"]
    return f"{noc['rows']}x{noc['cols']}_mc{noc['num_mcs']}"


def sweep_grid(config: dict, traffic: dict, **overrides):
    """The ``SweepGrid`` of the cell: the configuration's mesh, model and
    precision crossed with the traffic mix's axes."""
    from repro.noc import SweepGrid, mesh_by_name
    noc = config["noc"]
    base = mesh_by_name(mesh_name(config))
    have = {"num_vcs": base.num_vcs, "vc_depth": base.vc_depth,
            "lanes": base.lanes}
    want = {k: noc[k] for k in have}
    if have != want:
        raise ValueError(f"mesh {mesh_name(config)} resolves to {have}, "
                         f"the configuration states {want}")
    kw = dict(meshes=(mesh_name(config),), models=(config["model"],),
              precisions=(config["precision"],), max_packets_per_layer=None)
    kw.update(traffic["grid"])
    kw.update(overrides)
    return SweepGrid(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in kw.items()})


def cell_layers(config: dict, seed: int):
    """The cell's operand traffic on the seed's image (``operands.py``):
    the program's ``LayerTraffic`` list, and the same operands as host
    float32 (inputs, weights) pairs for the reference."""
    import jax.numpy as jnp
    from operands import cell_operands
    from repro.noc.traffic import LayerTraffic
    host = cell_operands(config, seed)
    return [LayerTraffic(jnp.asarray(i), jnp.asarray(w)) for i, w in host], host


def layer_shapes(host: List[Tuple[np.ndarray, np.ndarray]]):
    return [tuple(i.shape) for i, _ in host]
