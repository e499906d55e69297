"""The one module that touches jax APIs outside its stable core.

Supported-version policy (see ROADMAP.md): the repo runs on **jax
0.9.0** with libtpu 0.0.34, the toolchain CI pins and the TPU hosts
carry.  Every API below is experimental, private or was renamed in
recent releases, so it is funnelled through this module; no other
module may import it directly (``scripts/check_compat_imports.py``
enforces it), and a jax upgrade stays a one-file change.

  =====================  ==============================================
  name                   jax spelling
  =====================  ==============================================
  ``shard_map``          ``jax.shard_map``
  ``axis_size(name)``    ``lax.axis_size(name)``
  ``pvary(x, names)``    ``lax.pcast(x, names, to="varying")``
  ``element_block_spec`` ``pl.BlockSpec`` with ``pl.Element`` dims
  AOT persistence        ``jax.experimental.serialize_executable``
  ``tpu_chips_on_host``  ``jax._src.hardware_utils`` (PCI scan)
  ``use_compile_cache``  ``jax_compilation_cache_dir`` config
  =====================  ==============================================

The AOT surface feeds the persistent design store
(:mod:`repro.runtime.store`): a compiled executable is serialized whole,
and deserializing it skips tracing *and* compilation (milliseconds to
first result).
"""
from __future__ import annotations

import os
import pickle
from pathlib import Path
from typing import Callable, Sequence

import jax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental import serialize_executable as _se

shard_map = jax.shard_map
axis_size = lax.axis_size


def pvary(x, axis_names: Sequence[str]):
    """Cast ``x`` to device-varying along ``axis_names`` (shard_map
    replication typing of loop carries)."""
    return lax.pcast(x, tuple(axis_names), to="varying")


# --------------------------------------------------------------------------
# AOT compile / serialize / deserialize (persistent design store)
# --------------------------------------------------------------------------

#: The executable-serialization kind the store records with each entry.
AOT_KIND = "executable"


def aot_compile(jitted, sample_args):
    """Explicit AOT compile of a jitted callable for concrete/abstract
    args; the returned executable is what :func:`aot_serialize`
    persists."""
    return jitted.lower(sample_args).compile()


def aot_serialize(compiled) -> tuple[str, bytes]:
    """Serialize a compiled design to ``(kind, blob)``."""
    payload, in_tree, out_tree = _se.serialize(compiled)
    return AOT_KIND, pickle.dumps((payload, in_tree, out_tree))


def aot_deserialize(kind: str, blob: bytes):
    """Rehydrate a persisted design into a callable executable.

    Raises ``ValueError`` for a kind this module did not write — callers
    treat that as a store miss and recompile from the persisted ranking.
    """
    if kind != AOT_KIND:
        raise ValueError(f"unknown persisted-executable kind {kind!r}")
    payload, in_tree, out_tree = pickle.loads(blob)
    return _se.deserialize_and_load(payload, in_tree, out_tree)


# --------------------------------------------------------------------------
# Non-blocking completion polling (continuous-batching reap path)
# --------------------------------------------------------------------------


def is_ready(x) -> bool:
    """Non-blocking poll: has a dispatched device value finished computing?

    True when every leaf of ``x`` reports complete — a following
    ``jax.block_until_ready`` / runner ``finalize`` returns without
    waiting.  Leaves without ``is_ready`` (host arrays) are ready.
    """
    for leaf in jax.tree_util.tree_leaves(x):
        ready = getattr(leaf, "is_ready", None)
        if callable(ready):
            try:
                if not ready():
                    return False
            except Exception:
                continue   # polling is advisory: fall back to "ready"
    return True


# --------------------------------------------------------------------------
# Element-indexed Pallas BlockSpec (overlapping input blocks)
# --------------------------------------------------------------------------


def element_block_spec(
    block_shape: Sequence[int], index_map: Callable[..., tuple]
) -> pl.BlockSpec:
    """A ``BlockSpec`` whose ``index_map`` returns **element** offsets.

    Blocked (default) indexing places block ``i`` at ``index_map(i) *
    block_shape`` — it cannot express overlapping input windows (block
    stride != block size), which the fused stencil kernel needs for its
    halo rows.
    """
    return pl.BlockSpec(
        tuple(pl.Element(int(n)) for n in block_shape), index_map
    )


# --------------------------------------------------------------------------
# TPU chips attached to this host, without starting a JAX backend
# --------------------------------------------------------------------------


def tpu_chips_on_host() -> int:
    """Number of TPU chips on this host's PCI bus.

    Reads sysfs only, so a process that must leave the chips to its
    children (the serving router) can count them without initialising
    a backend — initialising one would take a chip.
    """
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


# --------------------------------------------------------------------------
# Persistent compilation cache
# --------------------------------------------------------------------------

#: Where compiled programs are cached when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed, git-ignored path inside the checkout.  The directory is
#: part of the cache key, so a path that moved between runs never hits.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: JAX reads it
    itself and nothing is set here.  Otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE`.  Entry points call this before their
    first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
