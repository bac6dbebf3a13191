"""``ddl25spring_tpu.serve`` — the continuous-batching decode engine
(ROADMAP item 3): a page pool of whatever planes the served model
declares (:mod:`.kv_pages`), the seam through which a model offers its
paged block (:mod:`.paged_model`), the prefill/decode-disaggregated
scheduler with admission control (:mod:`.engine`), and the seeded
synthetic open-loop workload (:mod:`.traffic`).  Two families are served:
the dense LLaMA block (``models/llama_paged.py``) and the
Mistral-Small-4 block with latent pages and routed experts
(``models/mistral4.py``).  Drive it via ``bench.py --serve``; report with
``tools/serve_report.py``; the benchmark's cells are ``python3
benchmark/run.py --workload olmo1b-serve-closed32`` and ``--workload
mistral4-serve-decode64``.

PEP-562 lazy exports (matching :mod:`ddl25spring_tpu.ft`): importing
the package must not drag jax in — :mod:`.traffic` is numpy-only and
``tools/serve_report.py`` is stdlib-only by contract.
"""

from __future__ import annotations

_LAZY = {
    "ServeEngine": ("ddl25spring_tpu.serve.engine", "ServeEngine"),
    "Request": ("ddl25spring_tpu.serve.engine", "Request"),
    "make_decode_tick": ("ddl25spring_tpu.serve.engine", "make_decode_tick"),
    "make_prefill": ("ddl25spring_tpu.serve.engine", "make_prefill"),
    "init_page_pool": ("ddl25spring_tpu.serve.kv_pages", "init_page_pool"),
    "TrafficSpec": ("ddl25spring_tpu.serve.traffic", "TrafficSpec"),
    "synth_trace": ("ddl25spring_tpu.serve.traffic", "synth_trace"),
}

__all__ = sorted(_LAZY)


def __getattr__(name: str):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    import importlib

    return getattr(importlib.import_module(mod_name), attr)
