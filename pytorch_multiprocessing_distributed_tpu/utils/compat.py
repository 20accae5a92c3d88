"""XLA's per-program analyses as plain dicts.

The package is written against the one installed jax (0.9.0) and calls
its surface directly (``jax.shard_map``, ``jax.lax.pcast``,
``jax.set_mesh`` ...). What stays here is the shape the benchmark, the
graftmeter budgets and the auditor share for a compiled program's cost
and memory models.
"""

from __future__ import annotations

_MEMORY_FIELDS = {
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "temp_bytes": "temp_size_in_bytes",
    "alias_bytes": "alias_size_in_bytes",
    "generated_code_bytes": "generated_code_size_in_bytes",
}


def cost_analysis_dict(compiled):
    """``compiled.cost_analysis()`` as a plain dict, or None when the
    backend has no cost model for the executable (callers treat cost
    as optional; an error from the call itself is a bug to see)."""
    analyses = compiled.cost_analysis()
    return dict(analyses) if analyses else None


def memory_analysis_dict(compiled):
    """``compiled.memory_analysis()`` as ONE plain dict of ints::

        {"argument_bytes", "output_bytes", "temp_bytes",
         "alias_bytes", "generated_code_bytes", "peak_bytes"}

    ``peak_bytes`` is the program's resident-HBM high-water estimate:
    arguments + outputs + temporaries + generated code, minus the
    aliased (donated) bytes the outputs share with the arguments —
    the number a capacity plan charges per resident program. None when
    the backend exposes no memory model, or only part of one (a partial
    memory model is not a budget)."""
    stats = compiled.memory_analysis()
    values = {key: getattr(stats, attr, None)
              for key, attr in _MEMORY_FIELDS.items()}
    if any(v is None for v in values.values()):
        return None
    out = {key: int(v) for key, v in values.items()}
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         + out["temp_bytes"]
                         + out["generated_code_bytes"]
                         - out["alias_bytes"])
    return out
