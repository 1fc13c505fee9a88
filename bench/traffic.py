"""One general generator for every traffic mix, and the finder of what a
mix names.

A mix is a JSON file ``bench/traffic/<mix>.json``. Its ``kind`` names the
driver that runs the cell, ``bench/drivers/<kind>.py``. A mix of requests
sent on a schedule names, the same way, its arrival process
(``"arrival": {"process": p, "rate_rps": r, ...}``, in
``bench/arrivals/<p>.py``) and its distribution of rows per request
(``"rows": {"dist": d, ...}``, in ``bench/rows/<d>.py``). A new driver,
process or distribution is a new file; a new mix of the existing ones is a
data file alone.

The work is the same for every seed: request sizes and gaps are drawn from
the mix's own ``work_seed``, and the run's seed only orders them and picks
the query rows.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict

import numpy as np

from bench.data import sub_seed

HOME = os.path.dirname(os.path.abspath(__file__))


def plugin(home: str, kind: str, name: str):
    """The module ``<home>/<kind>/<name>.py``, found by name."""
    path = os.path.join(home, kind, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(path: str, home: str = HOME) -> Dict:
    """A mix, refused unless its driver is found under ``home``."""
    with open(path) as f:
        mix = json.load(f)
    plugin(home, "drivers", str(mix.get("kind")))
    return mix


@dataclasses.dataclass
class Schedule:
    """Requests of one run, in sending order."""

    arrival_s: np.ndarray       # (R,) due time after the window opens
    rows: np.ndarray            # (R,) rows per request
    offset: np.ndarray          # (R,) first row in the query pool


def _rows(mix: Dict, n: int, seed: int, home: str):
    """Rows of ``n`` requests, in the seed's order, and the seed's order
    stream (which goes on to order the arrivals and pick the rows)."""
    spec = mix["rows"]
    work = np.random.default_rng(mix["work_seed"])
    rows = plugin(home, "rows", spec["dist"]).draw(spec, n, work)
    order = np.random.default_rng(sub_seed(seed, 1))
    return rows[order.permutation(n)], work, order


def open_loop(mix: Dict, seconds: float, seed: int, pool_rows: int,
              home: str = HOME) -> Schedule:
    """The run's requests: ``round(rate * seconds)`` of them, due over
    ``seconds`` as the mix's arrival process spaces them."""
    arr = mix["arrival"]
    n = max(1, int(round(arr["rate_rps"] * seconds)))
    rows, work, order = _rows(mix, n, seed, home)
    t = plugin(home, "arrivals", arr["process"]).times(arr, n, seconds,
                                                        work, order)
    offset = order.integers(0, pool_rows - rows + 1)
    return Schedule(arrival_s=t, rows=rows, offset=offset)


def warm_rows(mix: Dict, n: int, seed: int, pool_rows: int,
              home: str = HOME) -> Schedule:
    """``n`` requests of the mix's sizes with no due times (set-up)."""
    rows, _, order = _rows(mix, n, seed, home)
    offset = order.integers(0, pool_rows - rows + 1)
    return Schedule(arrival_s=np.zeros(n), rows=rows, offset=offset)
