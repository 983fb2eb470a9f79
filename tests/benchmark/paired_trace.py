"""The paired sums of a hand-made trace (PR 52).

``trace_reduce.reduce`` hands the readers, beside the sums over everything
a trace holds, the admissions it pairs with their ``batcher.admit.row`` span
and the whole decode programs, each with the kernels' seconds inside it; the
readers that set prompt tokens against device time read those and nothing
from ``trace_counters``.  A test that hands a reader a made-up ``TRACE``
(``op_s`` / ``module_count``) gives it the same sums through here: every
decode program whole, one paired admission for each entry of ``tokens``.
"""

from benchmark import trace_reduce


def paired(trace, tokens, admit_ops=(), program="jit_admit_row_paged"):
    """``trace`` with ``decode`` and ``admissions``: the kernels named in
    ``admit_ops`` spend their seconds inside the admissions (evenly), every
    other operation inside the decode programs, so that a kernel's seconds
    inside the programs are all of its ``op_s``."""
    decode = sum(n for name, n in trace["module_count"].items()
                 if name.startswith(trace_reduce.DECODE))
    return {
        **trace,
        "decode": {"count": decode, "seconds": 0.0,
                   "op_s": {k: v for k, v in trace["op_s"].items()
                            if k not in admit_ops}},
        "admissions": [{
            "rid": i, "program": program, "seconds": 0.0, "tokens": n,
            "bucket": None, "live_rows": None,
            "op_s": {k: trace["op_s"][k] / len(tokens) for k in admit_ops
                     if k in trace["op_s"]},
        } for i, n in enumerate(tokens)],
    }
