"""Writing a configuration's lake through the program, and reading back the
footer facts the benchmark's byte functions need."""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np


def write_lake(root: str, cfg: dict, data: dict):
    """Write the generated arrays as the configuration's sharded lake with
    the program's own writer (transactional catalog, format v2)."""
    from repro.core.columnar import from_ragged
    from repro.dataset import write_dataset

    cols = from_ragged(data["types"], data["coords"], data["part_sizes"],
                       data["parts_per_record"])
    return write_dataset(root, columns=cols, extra=data["extras"],
                         n_shards=int(cfg["n_shards"]), sort=cfg["sort"],
                         page_values=int(cfg["page_values"]))


def stored_bytes(scanner) -> int:
    """Bytes of the head snapshot's shard files."""
    from repro.dataset.manifest import shard_path

    return sum(os.path.getsize(shard_path(scanner.root, s))
               for s in scanner.manifest.shards)


@dataclass
class PageTable:
    """Every coordinate page of the lake, from the shard footers: its box,
    the stored bytes of its x and y pages, its records, and its zone
    statistics on the filter column."""

    xmin: np.ndarray
    ymin: np.ndarray
    xmax: np.ndarray
    ymax: np.ndarray
    nbytes: np.ndarray
    records: np.ndarray
    zmin: np.ndarray
    zmax: np.ndarray


def page_table(scanner, filter_column: str) -> PageTable:
    cols = {k: [] for k in ("xmin", "ymin", "xmax", "ymax", "nbytes",
                            "records", "zmin", "zmax")}
    for shard_i in range(len(scanner.manifest.shards)):
        with scanner.open_shard(shard_i) as r:
            footer = r.footer
        for rg in footer["row_groups"]:
            for px, py, pz in zip(rg["x_pages"], rg["y_pages"],
                                  rg["extra"][filter_column]):
                cols["xmin"].append(px["vmin"])
                cols["xmax"].append(px["vmax"])
                cols["ymin"].append(py["vmin"])
                cols["ymax"].append(py["vmax"])
                cols["nbytes"].append(px["nbytes"] + py["nbytes"])
                cols["records"].append(px["rec_count"])
                cols["zmin"].append(pz["vmin"])
                cols["zmax"].append(pz["vmax"])
    arr = {k: np.asarray(v, np.float64 if k[0] in "xyz" else np.int64)
           for k, v in cols.items()}
    return PageTable(**arr)
