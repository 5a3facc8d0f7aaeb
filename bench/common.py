"""What every cell of the benchmark shares: finding cells, configurations
and metric readers by name, the chip check, the compile cache, compile
counting, seeds, weights, and the result line.

Nothing here imports JAX at module level: ``run.py`` must set the
compile-cache directory before JAX starts.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
# Fixed, inside the checkout: the path is part of every cache entry's key.
# A directory of the benchmark's own: the program's tests and launcher
# write to ``.jax_cache`` without the access times that JAX's cache, once
# given a size limit, reads for every entry before it writes one.
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache_bench")


class NoChip(SystemExit):
    """The run found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: str = BENCH) -> dict:
    """The cell file ``<root>/workloads/<name>.json`` with its configuration
    file ``<root>/configs/<config>.json`` loaded under ``"cfg"``."""
    path = os.path.join(root, "workloads", name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no cell file {path}")
    cell = load_json(path)
    cell["name"] = name
    cell["cfg"] = load_json(os.path.join(root, "configs", cell["config"] + ".json"))
    return cell


def load_module(path: str, name: str):
    """Import a file by path (metric readers and references have dots or
    dashes in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_readers(names, root: str = BENCH) -> dict:
    return {
        n: load_module(os.path.join(root, "metrics", n + ".py"), "metric_" + n)
        for n in names
    }


def reference_module(cell: dict, root: str = BENCH):
    ref = cell["cfg"]["reference"]
    return load_module(os.path.join(root, "refs", ref + ".py"), "ref_" + ref)


def import_program() -> None:
    """Put the checkout's ``src`` on the path; no program, no run."""
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def use_cache() -> None:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``, for
    every program however small; must run before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def require_chip(chips: int) -> dict:
    """The device as JAX reports it; no TPU, or fewer chips than the
    cell's, exits non-zero before anything is printed on stdout."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return device_info(devs[:chips])


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**64 (two 32-bit folds)."""
    import jax
    import numpy as np

    if not 0 <= seed < 2 ** 64:
        raise SystemExit(f"bench: --seed {seed} outside [0, 2**64)")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed >> 32))
    return jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))


class CompileCounter:
    """Backend compiles and persistent-cache hits and misses, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses}


def weights_fn(shapes, init: dict):
    """``key -> weights`` in the program's parameter layout, each leaf in
    its own dtype, for one jitted call on the device. ``init`` maps a
    leaf's name (the last key of its path) to ``{"mean", "std"}``."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(path[-1].key) for path, _ in flat]
    missing = sorted(set(names) - set(init))
    if missing:
        raise SystemExit(f"bench: no init for leaves {missing}")

    def make(key):
        out = []
        for i, ((_, sd), n) in enumerate(zip(flat, names)):
            k = jax.random.fold_in(key, i)
            x = init[n]["mean"] + init[n]["std"] * jax.random.normal(
                k, sd.shape, jnp.float32)
            out.append(x.astype(sd.dtype))
        return jax.tree.unflatten(treedef, out)

    return make


@dataclasses.dataclass
class Check:
    """One number compared with its limit; a cell whose limit has not been
    set from chip readings yet (``null``) is never correct."""

    name: str
    value: float
    limit: float | None

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


def emit(result: dict, checks: list) -> None:
    """Each compared number beside its limit, last on stderr, then the
    result line, last on stdout, with the checks under its last key."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), flush=True)
