"""Faults planted under the timed path, for the test that sees ``correct``
come out false (``benchmark/tests``).  The driver's runs never name one.

``half_batch``      half of the partition's rows left out of the query
``altered_answer``  one value of the answer altered where it is produced
"""

import numpy as np


def wrap(state, name):
    """``state.query`` with fault ``name`` under it."""
    if not name:
        return state.query
    import jax

    if name == "altered_answer":
        def query(part, q, spans):
            out = state.query(part, q, spans)
            c = state.mod.RESULT_COLUMNS[1]
            d, v, t = out[c]
            d = np.array(d)
            d[0] += 1
            out[c] = (d, v, t)
            return out
        return query

    if name == "half_batch":
        rows = state.rows
        def cut(batch):
            if batch.num_rows != rows:      # a dimension: left whole
                return batch
            return jax.tree_util.tree_map(lambda a: a[: rows // 2], batch)

        half = [{nm: cut(b) for nm, b in inputs.items()}
                for inputs in state.inputs]
        return lambda part, q, spans: state.query(part, q, spans,
                                                  inputs=half[part])

    raise ValueError(f"no fault {name!r}")
