"""AOT-serialized executables (FLAGS_aot_cache_dir) — zero-compile
restarts.

The warm path so far: FLAGS_compile_cache_dir persists XLA's compiled
artifacts, so a restarted process skips the XLA compile — but it still
pays the Python Program→jaxpr trace per signature, and the cache is
keyed deep inside jax.  This module goes the rest of the way for fleet
restarts (ROADMAP "AOT-serialize the compiled executables so N replicas
boot without N compiles"): the executor serializes each compiled
executable (`jax.experimental.serialize_executable` — the loaded object
is CALLABLE, no re-trace, no re-compile) keyed by a STABLE signature —
program fingerprint (op types + process-independent attrs), the jitted
call's argument specs, the fetch list, and the platform/jaxlib identity.
A restarted replica's first request deserializes and runs: the
`pt_compile_cache_total{result="aot_hit"}` counter books the hit and
NO `result="miss"` / `phase="aot_compile"` cost appears — the
measurable zero-compile contract (tests/test_aot_warmstart.py).

Scope and caveats:
- per-step executables only (`Executor.run`); `run_steps` chains and
  the mesh runners keep the warm-cache story.
- the payload embeds a machine-compiled executable: the key includes
  backend platform, device kind and the jaxlib version, and the cache
  dir must not be shared across heterogeneous hosts.
- every failure path (toolchain without the API, stale/corrupt file,
  cross-version payload) warns once and falls back to the normal
  compile path — a broken cache dir must never stop a run.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import threading
import warnings

__all__ = ["enabled", "available", "executable_key", "load", "save",
           "program_fingerprint"]

_SUFFIX = ".aotx"
_warned = set()
_warn_lock = threading.Lock()


def _warn_once(tag, msg):
    with _warn_lock:
        if tag in _warned:
            return
        _warned.add(tag)
    warnings.warn(msg)


def available():
    """The jax toolchain can (de)serialize compiled executables."""
    try:
        from jax.experimental import serialize_executable  # noqa: F401

        return True
    except Exception:  # pragma: no cover - toolchain-specific
        return False


def cache_dir():
    from . import flags as _flags

    return _flags.flag("aot_cache_dir") or None


def enabled():
    return bool(cache_dir()) and available()


def _stable(v):
    """Only attr payloads whose repr is process-independent join the
    fingerprint (the serving model_signature contract — a Variable or
    sub-block repr can embed a memory address)."""
    if isinstance(v, (bool, int, float, str, bytes, type(None))):
        return True
    if isinstance(v, (list, tuple)):
        return all(_stable(x) for x in v)
    return False


def program_fingerprint(program):
    """Restart-stable hash of a program: op types + per-slot in/out
    wiring + stable attrs + var specs, over every block.  The wiring
    matters: two programs with identical op sequences, attrs and var
    sets but swapped operands (matmul(x,W1)->t0 vs matmul(x,W2)->t0)
    must NOT share an executable."""
    h = hashlib.sha1()
    for b in program.blocks:
        for op in b.ops:
            h.update(op.type.encode())
            h.update(b"\x00")
            for slot in sorted(op.inputs):
                h.update(f"i:{slot}={op.inputs[slot]!r}".encode())
                h.update(b"\x00")
            for slot in sorted(op.outputs):
                h.update(f"o:{slot}={op.outputs[slot]!r}".encode())
                h.update(b"\x00")
            for k in sorted(op.attrs):
                v = op.attrs[k]
                if _stable(v):
                    h.update(f"{k}={v!r}".encode())
                    h.update(b"\x00")
        for name in sorted(b.vars):
            v = b.vars[name]
            h.update(repr((name, tuple(v.shape or ()) if v.shape else (),
                           v.dtype, bool(v.persistable))).encode())
            h.update(b"\x00")
    return h.hexdigest()


# kernel-implementation override envs: these select WHAT gets lowered
# for the same program (Pallas vs XLA reference paths), so a serialized
# executable is only valid under the same settings — a key without them
# would silently serve a Pallas-path executable to a
# PT_FUSED_UPDATE_IMPL=xla debug run (or the inverse in production)
_IMPL_ENVS = ("PT_FLASH_FORCE_PALLAS", "PT_FUSED_UPDATE_IMPL",
              "PT_RNG_IMPL")


def _platform_tag():
    import jax
    import jaxlib

    from .platform_utils import default_platform

    impls = ",".join(f"{e}={os.environ.get(e, '')}" for e in _IMPL_ENVS)
    return (f"{default_platform()}|{jax.devices()[0].device_kind}|"
            f"jax{jax.__version__}|jaxlib{jaxlib.__version__}|{impls}")


def executable_key(program, arg_specs, fetch_names):
    """The on-disk key: program fingerprint x argument specs x fetch
    list x platform identity.  `arg_specs` is the jitted call's spec
    pytree (donated/readonly/feed ShapeDtypeStructs) — it pins every
    shape/dtype the executable was specialized to."""
    import jax

    h = hashlib.sha1()
    h.update(program_fingerprint(program).encode())
    leaves, treedef = jax.tree.flatten(arg_specs)
    h.update(str(treedef).encode())
    for leaf in leaves:
        h.update(repr((tuple(leaf.shape), str(leaf.dtype))).encode())
        h.update(b"\x00")
    h.update(repr(tuple(fetch_names)).encode())
    h.update(_platform_tag().encode())
    return h.hexdigest()


def _path(key):
    return os.path.join(cache_dir(), key + _SUFFIX)


def load(key):
    """-> a callable compiled executable, or None (absent / unloadable;
    unloadable warns once and is deleted so the next save can heal)."""
    if not enabled():
        return None
    path = _path(key)
    if not os.path.exists(path):
        return None
    try:
        from jax.experimental import serialize_executable as se

        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        return se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception as e:  # resilience: allow — cache is best-effort
        _warn_once("load:" + key,
                   f"AOT executable {path} failed to load ({e!r}); "
                   f"falling back to compile and replacing it")
        try:
            os.unlink(path)
        except OSError:
            pass
        return None


def save(key, compiled):
    """Serialize `compiled` under `key` (atomic temp+rename — a crashed
    save never truncates a good entry).  Best-effort: failures warn
    once."""
    if not enabled():
        return False
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        d = cache_dir()
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".{key}.{os.getpid()}.tmp")
        with open(tmp, "wb") as f:
            pickle.dump((payload, in_tree, out_tree), f)
        os.replace(tmp, _path(key))
        return True
    except Exception as e:  # resilience: allow — cache is best-effort
        _warn_once("save:" + key,
                   f"AOT executable save failed ({e!r}); the run "
                   f"continues uncached")
        return False
