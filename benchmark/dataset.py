"""The graph of a configuration and the program's decomposition of it.

Both are made once per checkout and kept under ``benchmark/cache/``:
the generated adjacency (the reference's input) and the program's
saved decomposition (``io.save_decomposition``).  The cache key holds
the configuration and a digest of the generator and of the program's
decomposition and I/O sources, so a changed decomposer never reads a
stale artifact.  Every run, the first included, loads the decomposition
through the program's loader, so the ``load`` span is the same work in
each.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import shutil
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, "cache")
# Program sources whose change must invalidate a cached decomposition.
KEYED_SOURCES = ("arrow_matrix_tpu/decomposition", "arrow_matrix_tpu/io")


@dataclass
class Dataset:
    levels: list        # the program's ArrowLevel list
    width: int
    n: int
    nnz: int
    adjacency: str      # directory holding indptr.npy / indices.npy


def _digest_tree(h, rel: str) -> None:
    top = os.path.join(ROOT, rel)
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if name.endswith((".py", ".cpp")):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, ROOT).encode())
                with open(path, "rb") as f:
                    h.update(f.read())


def generator_path(cfg: dict) -> str:
    return os.path.join(HERE, "graphs", cfg["generator"] + ".py")


def cache_key(cfg: dict) -> str:
    h = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    with open(generator_path(cfg), "rb") as f:
        h.update(f.read())
    for rel in KEYED_SOURCES:
        _digest_tree(h, rel)
    return h.hexdigest()[:16]


def _generate(cfg: dict):
    spec = importlib.util.spec_from_file_location(
        "benchmark_graph_" + cfg["generator"], generator_path(cfg))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate(cfg, cfg["graph_seed"])


def _build(cfg: dict, out: str, spans) -> None:
    """Generate, decompose and save into ``out`` (a fresh directory)."""
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.io import save_decomposition

    with spans("generate"):
        a = _generate(cfg)
    np.save(os.path.join(out, "indptr.npy"), a.indptr.astype(np.int64))
    np.save(os.path.join(out, "indices.npy"), a.indices.astype(np.int32))
    with spans("decompose"):
        levels = arrow_decomposition(
            a, arrow_width=cfg["arrow_width"],
            max_levels=cfg["max_levels"],
            block_diagonal=cfg["block_diagonal"],
            seed=cfg["graph_seed"], backend=cfg["decompose_backend"])
    save_decomposition(levels, os.path.join(out, "arrow"),
                       block_diagonal=cfg["block_diagonal"])
    with open(os.path.join(out, "meta.json"), "w") as f:
        json.dump({"n": int(a.shape[0]), "nnz": int(a.nnz),
                   "levels": len(levels),
                   "level_nnz": [int(lvl.matrix.nnz) for lvl in levels]},
                  f)


def prepare(cfg: dict, spans) -> Dataset:
    """Load the configuration's decomposition, making it first when this
    checkout has none for the current key."""
    from arrow_matrix_tpu.io import (
        as_levels,
        load_decomposition,
        load_level_widths,
    )

    d = os.path.join(CACHE, "graphs", cfg["name"], cache_key(cfg))
    if not os.path.exists(os.path.join(d, "meta.json")):
        tmp = d + ".partial"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        _build(cfg, tmp, spans)
        os.replace(tmp, d)
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    width, bd = cfg["arrow_width"], cfg["block_diagonal"]
    base = os.path.join(d, "arrow")
    with spans("load"):
        loaded = load_decomposition(base, width, block_diagonal=bd)
        widths = load_level_widths(base, width, block_diagonal=bd)
        levels = as_levels(loaded, widths if widths is not None else width)
    return Dataset(levels=levels, width=width, n=meta["n"],
                   nnz=meta["nnz"], adjacency=d)


def load_adjacency(ds: Dataset) -> tuple[np.ndarray, np.ndarray]:
    return (np.load(os.path.join(ds.adjacency, "indptr.npy")),
            np.load(os.path.join(ds.adjacency, "indices.npy")))
