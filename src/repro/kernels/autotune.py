"""Measured-search autotuner for the Pallas kernel tile sizes.

Every kernel wrapper in this package (``gram_op``, ``project_op``/
``project_partial_op``, ``center_op``) historically ran one hardcoded
tiling (128x128x512, centering 256). That is a fine default on TPU-sized
problems and provably NOT optimal everywhere else — tile choice is a
hardware/shape question, so it is answered by measurement:

  * a candidate grid per op, filtered by what Mosaic compiles for the
    concrete padded problem: sublane (row) tiles in multiples of 8; lane
    tiles — the output's last axis, which also carries the (1, k) norm
    row — in multiples of 128 or the whole padded axis; feature tiles in
    multiples of 128; and no tile wider than the padded axis (anything
    larger is the same program after the wrappers' auto-shrink);
  * each candidate timed best-of-``k`` with ``jax.block_until_ready`` on
    the actual output (compile excluded by an untimed warmup call);
  * winners persisted to a JSON **tile table** keyed by
    ``(op, pow2-shape-bucket, dtype, backend)`` and loaded transparently
    by the wrappers — a tuned entry changes the dispatch of every later
    call with that key, callers change nothing;
  * no entry -> the historical defaults, so an empty/missing table is
    exactly the pre-autotune behavior.

Point ``REPRO_TILE_TABLE`` at a table file to load it process-wide (read
once, before the first kernel dispatch), or install one programmatically
with ``set_default_table``. ``python -m repro.kernels.autotune --out
tile_table.json`` searches the standard serving shapes; tuning a shape
whose key is already in the table is a cache hit and re-runs nothing
(``force=True`` overrides).

Observability: every trial bumps ``autotune_trials_total`` and runs under
an ``autotune.<op>`` trace span; cache hits bump ``autotune_cached_total``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import metrics, trace

# Historical fixed tilings — the fallback for every key the table misses.
DEFAULT_TILES: Dict[str, Dict[str, int]] = {
    "gram": {"block_n": 128, "block_k": 128, "block_m": 512},
    "project": {"block_q": 128, "block_l": 128, "block_m": 512},
    "project_partial": {"block_q": 128, "block_l": 128, "block_m": 512},
    "centering": {"block": 256},
}

TABLE_ENV_VAR = "REPRO_TILE_TABLE"
TABLE_VERSION = 1

_m_trials = metrics.counter(
    "autotune_trials_total", "Tile candidates timed by the autotuner")
_m_cached = metrics.counter(
    "autotune_cached_total", "Tune requests answered from the tile table")


def _pow2_bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= n (and >= floor) — the shape-bucket axis
    of a tile-table key. Serving already quantizes batch to pow2 buckets,
    so in steady state the bucket IS the padded shape."""
    b = floor
    while b < n:
        b *= 2
    return b


def shape_bucket(shape: Sequence[int]) -> Tuple[int, ...]:
    """Bucket every dim of ``shape`` to a power of two."""
    return tuple(_pow2_bucket(int(d)) for d in shape)


def table_key(op: str, shape: Sequence[int], dtype: Any,
              backend: str) -> str:
    """Canonical JSON key: ``op|d1xd2x...|dtype|backend`` with the shape
    pow2-bucketed."""
    dims = "x".join(str(d) for d in shape_bucket(shape))
    return f"{op}|{dims}|{np.dtype(dtype).name}|{backend}"


@dataclasses.dataclass
class Trial:
    """One timed candidate: its block sizes and best-of-k seconds."""
    blocks: Dict[str, int]
    seconds: float


class TileTable:
    """In-memory tile table with JSON round-trip.

    ``entries`` maps ``table_key`` strings to block-size dicts (plus the
    winning ``us`` for provenance). Thread-safety: lookups are plain dict
    reads (safe under the GIL); tuning writes happen before serving
    traffic in any sane deployment, and a racy overwrite of identical
    data is harmless.
    """

    def __init__(self, entries: Optional[Dict[str, Dict[str, Any]]] = None):
        self.entries: Dict[str, Dict[str, Any]] = dict(entries or {})

    # -- persistence --------------------------------------------------------

    @classmethod
    def load(cls, path: str) -> "TileTable":
        with open(path) as f:
            payload = json.load(f)
        if payload.get("version") != TABLE_VERSION:
            raise ValueError(
                f"tile table {path}: version {payload.get('version')!r} "
                f"!= supported {TABLE_VERSION}")
        return cls(payload.get("entries", {}))

    def save(self, path: str) -> None:
        payload = {"version": TABLE_VERSION, "entries": self.entries}
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")

    # -- lookup/update ------------------------------------------------------

    def lookup(self, op: str, shape: Sequence[int], dtype: Any,
               backend: str) -> Optional[Dict[str, int]]:
        hit = self.entries.get(table_key(op, shape, dtype, backend))
        if hit is None:
            return None
        return {k: int(v) for k, v in hit.items() if k.startswith("block")}

    def put(self, op: str, shape: Sequence[int], dtype: Any, backend: str,
            blocks: Dict[str, int], seconds: float) -> str:
        key = table_key(op, shape, dtype, backend)
        self.entries[key] = dict(blocks, us=round(seconds * 1e6, 3))
        return key

    def __len__(self) -> int:
        return len(self.entries)


# Process-wide table, initialized lazily from $REPRO_TILE_TABLE so launch
# env configuration (launch/env.py) can point every process of a
# deployment at one tuned table without code changes.
_default_table: Optional[TileTable] = None


def default_table() -> TileTable:
    global _default_table
    if _default_table is None:
        path = os.environ.get(TABLE_ENV_VAR)
        if path and os.path.exists(path):
            _default_table = TileTable.load(path)
        else:
            _default_table = TileTable()
    return _default_table


def set_default_table(table: Optional[TileTable]) -> None:
    """Install (or with None: reset, re-reading $REPRO_TILE_TABLE on next
    use) the process-wide table."""
    global _default_table
    _default_table = table


def get_tiles(op: str, shape: Sequence[int], dtype: Any,
              table: Optional[TileTable] = None) -> Dict[str, int]:
    """Tile sizes for one dispatch: table hit for this (op, shape-bucket,
    dtype, backend), else the historical defaults. This is the hook the
    ``ops.py`` wrappers call when no explicit block sizes are passed."""
    import jax
    backend = jax.default_backend()
    t = table if table is not None else default_table()
    hit = t.lookup(op, shape, dtype, backend)
    if hit is not None:
        return dict(DEFAULT_TILES[op], **hit)
    return dict(DEFAULT_TILES[op])


# ---- candidate grids ------------------------------------------------------

def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


_SUBLANE_TILES = (8, 16, 32, 64, 128, 256)
_LANE_TILES = (128, 256)
_FEATURE_TILES = (128, 256, 512)


def _tiles(choices: Sequence[int], padded: int) -> List[int]:
    """Tiles from ``choices`` that fit ``padded``; below the smallest
    choice the whole padded axis is the one tile (always legal)."""
    return [b for b in choices if b <= padded] or [padded]


def gram_candidates(n: int, k: int, m: int) -> List[Dict[str, int]]:
    """Legal (block_n, block_k, block_m) grid for an (n, m) x (k, m) gram.

    Legality: row tiles are multiples of 8 (sublane); column tiles are the
    output's lane axis, so multiples of 128 (or the whole axis below 128);
    feature tiles multiples of 128. Tiles beyond the padded axis are
    dropped — the wrapper's auto-shrink maps them to the same program as
    the axis-sized tile, so timing them twice is pure waste.
    """
    np_, kp, mp = _round_up(n, 8), _round_up(k, 8), _round_up(m, 128)
    bns = _tiles(_SUBLANE_TILES, np_)
    bks = _tiles(_LANE_TILES, kp)
    bms = _tiles(_FEATURE_TILES, mp)
    return [{"block_n": bn, "block_k": bk, "block_m": bm}
            for bn in bns for bk in bks for bm in bms]


def project_candidates(b: int, l: int, m: int) -> List[Dict[str, int]]:
    """Legal (block_q, block_l, block_m) grid for a (b, m) query batch
    against an (l, m) support set (same legality rules as gram: the
    support tile is the lane axis of the kernel block and of the norm
    row)."""
    bp, lp, mp = _round_up(b, 8), _round_up(l, 8), _round_up(m, 128)
    bqs = _tiles(_SUBLANE_TILES, bp)
    bls = _tiles(_LANE_TILES, lp)
    bms = _tiles(_FEATURE_TILES, mp)
    return [{"block_q": bq, "block_l": bl, "block_m": bm}
            for bq in bqs for bl in bls for bm in bms]


def centering_candidates(n: int, m: int) -> List[Dict[str, int]]:
    """Legal square-ish block grid for centering an (n, m) kernel matrix
    (one knob: the wrapper derives row/col tiles from it, and the column
    tile is a lane axis, so the knob is a multiple of 128)."""
    np_ = _round_up(n, 8)
    return [{"block": b} for b in (128, 256, 512) if b <= max(np_, 128)]


# ---- measurement ----------------------------------------------------------

def _time_best_of(fn, args, k: int) -> float:
    """Best-of-k wall seconds for ``fn(*args)``, blocked on the REAL
    output; one untimed warmup call eats the compile."""
    import jax
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(k):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _search(op: str, shape: Sequence[int], dtype: Any,
            candidates: List[Dict[str, int]], build_fn, k: int,
            table: Optional[TileTable], force: bool
            ) -> Tuple[Dict[str, int], List[Trial]]:
    """Shared search loop: cache-check, time every candidate, commit the
    winner. ``build_fn(blocks)`` returns a (fn, args) pair ready to time."""
    import jax
    backend = jax.default_backend()
    t = table if table is not None else default_table()
    if not force:
        hit = t.lookup(op, shape, dtype, backend)
        if hit is not None:
            _m_cached.inc()
            return dict(DEFAULT_TILES[op], **hit), []
    trials: List[Trial] = []
    with trace.span(f"autotune.{op}", shape=list(shape),
                    n_candidates=len(candidates)):
        for blocks in candidates:
            fn, args = build_fn(blocks)
            seconds = _time_best_of(fn, args, k)
            trials.append(Trial(dict(blocks), seconds))
            _m_trials.inc()
            if trace.is_enabled():
                trace.instant(f"autotune.{op}.trial", **blocks,
                              us=round(seconds * 1e6, 2))
    best = min(trials, key=lambda tr: tr.seconds)
    t.put(op, shape, dtype, backend, best.blocks, best.seconds)
    return dict(DEFAULT_TILES[op], **best.blocks), trials


def tune_gram(spec, x, y=None, gamma=None, interpret=None, k: int = 3,
              table: Optional[TileTable] = None, force: bool = False,
              candidates: Optional[List[Dict[str, int]]] = None
              ) -> Tuple[Dict[str, int], List[Trial]]:
    """Search the gram tile grid for this concrete problem; returns
    (winning blocks, trials — empty on a table cache hit)."""
    import jax
    from .gram.ops import gram_op
    yy = x if y is None else y
    shape = (x.shape[0], yy.shape[0], x.shape[1])
    cands = candidates if candidates is not None else gram_candidates(*shape)

    def build(blocks):
        fn = jax.jit(lambda xa, ya: gram_op(
            spec, xa, ya, gamma=gamma, interpret=interpret, **blocks))
        return fn, (x, yy)

    return _search("gram", shape, x.dtype, cands, build, k, table, force)


def tune_project(spec, x_query, x_support, coefs, row_mean_coef=None,
                 bias=None, gamma=None, interpret=None, k: int = 3,
                 table: Optional[TileTable] = None, force: bool = False,
                 candidates: Optional[List[Dict[str, int]]] = None
                 ) -> Tuple[Dict[str, int], List[Trial]]:
    """Search the fused-projection tile grid (serving hot path)."""
    import jax
    from .project.ops import project_op
    shape = (x_query.shape[0], x_support.shape[0], x_query.shape[1])
    cands = candidates if candidates is not None \
        else project_candidates(*shape)

    def build(blocks):
        fn = jax.jit(lambda xq: project_op(
            spec, xq, x_support, coefs, row_mean_coef=row_mean_coef,
            bias=bias, gamma=gamma, interpret=interpret, **blocks))
        return fn, (x_query,)

    return _search("project", shape, x_query.dtype, cands, build, k,
                   table, force)


def tune_project_partial(spec, x_query, x_support, coefs_ext, gamma=None,
                         interpret=None, k: int = 3,
                         table: Optional[TileTable] = None,
                         force: bool = False,
                         candidates: Optional[List[Dict[str, int]]] = None
                         ) -> Tuple[Dict[str, int], List[Trial]]:
    """Search the per-shard partial-projection tile grid."""
    import jax
    from .project.ops import project_partial_op
    shape = (x_query.shape[0], x_support.shape[0], x_query.shape[1])
    cands = candidates if candidates is not None \
        else project_candidates(*shape)

    def build(blocks):
        fn = jax.jit(lambda xq: project_partial_op(
            spec, xq, x_support, coefs_ext, gamma=gamma,
            interpret=interpret, **blocks))
        return fn, (x_query,)

    return _search("project_partial", shape, x_query.dtype, cands, build,
                   k, table, force)


def tune_centering(k_matrix, k: int = 3,
                   table: Optional[TileTable] = None, force: bool = False,
                   candidates: Optional[List[Dict[str, int]]] = None
                   ) -> Tuple[Dict[str, int], List[Trial]]:
    """Search the centering block grid for an (n, m) kernel matrix."""
    import jax
    from .centering.ops import center_op
    shape = tuple(k_matrix.shape)
    cands = candidates if candidates is not None \
        else centering_candidates(*shape)

    def build(blocks):
        fn = jax.jit(lambda km: center_op(km, interpret=None, **blocks))
        return fn, (k_matrix,)

    return _search("centering", shape, k_matrix.dtype, cands, build, k,
                   table, force)


# ---- CLI ------------------------------------------------------------------

def _standard_serving_shapes(m: int, landmarks: int, max_batch: int):
    """The pow2 serving buckets the engines actually dispatch."""
    b = 8
    while b < max_batch:
        yield b
        b *= 2
    yield max_batch


ALL_OPS = ("gram", "project", "project_partial", "centering")


def main(argv=None) -> None:
    """``python -m repro.kernels.autotune --out tile_table.json``: tune
    gram/project/project_partial/centering over the standard serving
    shapes and persist the table. Rerunning against an existing table only
    fills gaps; ``--assert-cached`` turns the rerun into a CI check that
    every requested key really answers from the table (0 trials)."""
    import argparse
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--out", default="tile_table.json")
    ap.add_argument("--m", type=int, default=64, help="feature dim")
    ap.add_argument("--landmarks", type=int, default=256,
                    help="support-set rows for project/gram")
    ap.add_argument("--max-batch", type=int, default=128,
                    help="widest serving bucket")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count sizing project_partial's per-shard "
                         "support slice (sharded serving dispatches the "
                         "partial op at landmarks/shards rows)")
    ap.add_argument("--ops", nargs="*", default=None, choices=ALL_OPS,
                    help="subset of ops to tune (default: all)")
    ap.add_argument("--k", type=int, default=3, help="timing repeats")
    ap.add_argument("--force", action="store_true",
                    help="re-search keys already in the table")
    ap.add_argument("--assert-cached", action="store_true",
                    help="fail unless every requested key is already a "
                         "table hit — the CI cache-hit assertion")
    args = ap.parse_args(argv)

    from ..core.kernels_math import KernelSpec
    spec = KernelSpec(kind="rbf", gamma=0.5)
    table = TileTable.load(args.out) if os.path.exists(args.out) \
        else TileTable()
    want = set(args.ops) if args.ops else set(ALL_OPS)
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(args.landmarks, args.m)).astype(np.float32)
    coefs = rng.normal(size=(args.landmarks, 4)).astype(np.float32)
    n_trials = 0

    if "gram" in want:
        blocks, trials = tune_gram(spec, xs, k=args.k, table=table,
                                   force=args.force)
        n_trials += len(trials)
        print(f"gram {xs.shape}: {blocks} ({len(trials)} trials)")
    # Per-shard slice for the sharded partial op: the serving path calls
    # project_partial_op with each shard's Lp = ceil(L/S) support rows.
    lp = max(8, -(-args.landmarks // max(args.shards, 1)))
    xs_shard = rng.normal(size=(lp, args.m)).astype(np.float32)
    coefs_ext = rng.normal(size=(lp, 5)).astype(np.float32)
    for b in _standard_serving_shapes(args.m, args.landmarks,
                                      args.max_batch):
        xq = rng.normal(size=(b, args.m)).astype(np.float32)
        if "project" in want:
            blocks, trials = tune_project(spec, xq, xs, coefs, k=args.k,
                                          table=table, force=args.force)
            n_trials += len(trials)
            print(f"project b={b}: {blocks} ({len(trials)} trials)")
        if "project_partial" in want:
            blocks, trials = tune_project_partial(
                spec, xq, xs_shard, coefs_ext, k=args.k, table=table,
                force=args.force)
            n_trials += len(trials)
            print(f"project_partial b={b} (Lp={lp}): {blocks} "
                  f"({len(trials)} trials)")
    if "centering" in want:
        km = rng.normal(size=(args.landmarks, args.landmarks)) \
            .astype(np.float32)
        blocks, trials = tune_centering(km, k=args.k, table=table,
                                        force=args.force)
        n_trials += len(trials)
        print(f"centering {km.shape}: {blocks} ({len(trials)} trials)")
    table.save(args.out)
    print(f"wrote {len(table)} entries -> {args.out}")
    if args.assert_cached and n_trials:
        raise SystemExit(
            f"--assert-cached: expected every key to hit the table, but "
            f"{n_trials} trials ran (stale or missing entries)")


__all__ = [
    "DEFAULT_TILES", "TABLE_ENV_VAR", "TileTable", "Trial",
    "centering_candidates", "default_table", "get_tiles",
    "gram_candidates", "project_candidates", "set_default_table",
    "shape_bucket", "table_key", "tune_centering", "tune_gram",
    "tune_project", "tune_project_partial",
]

if __name__ == "__main__":
    main()
