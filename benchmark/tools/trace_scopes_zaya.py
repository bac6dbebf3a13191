#!/usr/bin/env python3
"""``trace_scopes.py`` with the scopes and the kernel of the ``zaya`` block
added to its lists (``models/zaya.py``, ``models/routed_experts.py``), which
postdate them: without these every such operation folds into the container
``blocks``.

    python3 benchmark/tools/trace_scopes_zaya.py <cell>.xplane.pb --hlo DIR

Same arguments, same output; edits nothing.  The split PERF.md section 5
gives of the cell's tick: ``experts`` (the three ``moe_gmm`` calls, the
sort and the gathers around them), ``page_gather`` + ``page_write`` +
``attn`` (pages), ``router`` + ``cca_proj`` + ``cca_conv`` +
``cca_mean_norm`` + ``cca_shift`` (router and CCA), ``head`` + ``sample``,
and the idle gaps (host).  A ``benchmark`` PR that takes the names into
``trace_scopes.py``'s own lists makes this file needless.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_scopes  # noqa: E402

trace_scopes.SCOPES += (
    "cca_proj", "cca_conv", "cca_mean_norm", "cca_shift", "router", "experts",
)
trace_scopes.KERNELS += ("moe_gmm",)

if __name__ == "__main__":
    sys.exit(trace_scopes.main())
