"""Runtime package — lazy exports (PEP 562).

The decode engine (``runtime/decode.py``) is deliberately importable
without JAX or numpy: the race-smoke CI job explores its slot-
conservation invariants with no accelerator toolchain installed. Eager
re-exports here would drag ``registry``/``batcher`` (and therefore JAX)
into every ``ai4e_tpu.runtime.*`` import, so the package resolves its
public names on first attribute access instead.
"""

import importlib

_EXPORTS = {
    "BatcherSaturated": ".batcher",
    "MicroBatcher": ".batcher",
    "FAMILIES": ".families",
    "LM_FAMILIES": ".families",
    "build_servable": ".families",
    "crops_handoff": ".handoffs",
    "LadderManager": ".ladder",
    "ShapeHistogram": ".ladder",
    "derive_ladder": ".ladder",
    "ModelRuntime": ".registry",
    "ServableModel": ".registry",
    "enable_compilation_cache": ".registry",
    "InferenceWorker": ".worker",
    "DecodeEngine": ".decode",
    "DecodeSaturated": ".decode",
    "SlotPool": ".decode",
    "LMServable": ".kvcache",
    "PagedDecodeRuntime": ".kvcache",
    "build_lm_servable": ".kvcache",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        module = importlib.import_module(_EXPORTS[name], __name__)
        value = getattr(module, name)
        globals()[name] = value  # cache: later accesses skip this hook
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
