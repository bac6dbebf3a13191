#!/usr/bin/env python3
"""``trace_scopes.py`` with the scopes and kernels of the expert and hybrid
blocks added to its lists (``models/mistral4.py``, ``models/qwen3_next.py``,
``models/routed_experts.py``), which postdate them: without these every such
operation folds into the container ``blocks``.

    python3 benchmark/tools/trace_scopes_hybrid.py <cell>.xplane.pb --hlo DIR

Same arguments, same output; edits nothing.  A ``benchmark`` PR that takes
the names into ``trace_scopes.py``'s own lists makes this file needless.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_scopes  # noqa: E402

trace_scopes.SCOPES += (
    "gdn_proj", "gdn_conv", "gdn_step", "gdn_chunk", "gdn_out",
    "mla_q", "latent_write", "latent_gather", "router", "experts",
    "shared_expert",
)
trace_scopes.KERNELS += ("gdn_step", "moe_gmm")

if __name__ == "__main__":
    sys.exit(trace_scopes.main())
