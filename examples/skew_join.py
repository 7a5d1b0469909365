"""Skew join of X(A,B) ⋈ Y(B,C) on a heavy hitter (paper Example 3).

All tuples sharing the heavy-hitter B-value must pairwise meet.  The X2Y
planner packs the (different-sized) tuples into bins; each reducer joins
one X-bin with one Y-bin.

Run:  PYTHONPATH=src python examples/skew_join.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import plan_x2y, x2y_comm_lower_bound
from repro.mapreduce import skew_join


def main():
    rng = np.random.default_rng(2)
    mx, my = 180, 12          # heavy hitter: many X tuples, a few Y tuples
    # tuple payload sizes differ (wide vs narrow rows)
    wx = np.clip(rng.lognormal(-2.0, 0.6, mx), 0.01, 0.3)
    wy = np.clip(rng.lognormal(-1.2, 0.5, my), 0.05, 0.45)
    q = 1.0

    schema = plan_x2y(wx, wy, q)
    schema.validate("x2y", x_ids=range(mx), y_ids=range(mx, mx + my))
    lb = x2y_comm_lower_bound(wx, wy, q)
    print(f"heavy hitter join: |X|={mx}, |Y|={my}")
    print(f"schema             : {schema.algorithm}")
    print(f"reducers           : {schema.num_reducers} "
          f"(= x_bins {schema.meta['x_bins']} x y_bins {schema.meta['y_bins']})")
    print(f"communication cost : {schema.communication_cost():.2f} "
          f"(lower bound {lb:.2f}, ratio "
          f"{schema.communication_cost() / lb:.2f})")

    # execute: join payloads
    xv = jnp.asarray(rng.normal(size=(mx, 3)).astype(np.float32))
    yv = jnp.asarray(rng.normal(size=(my, 2)).astype(np.float32))
    out, _ = skew_join(xv, yv, q=q, wx=wx, wy=wy, schema=schema)
    assert out.shape == (mx, my, 5)
    # spot-check completeness of the join
    ok = np.allclose(np.asarray(out[17, 3, :3]), np.asarray(xv[17])) and \
        np.allclose(np.asarray(out[17, 3, 3:]), np.asarray(yv[3]))
    print(f"join output        : {out.shape} tuples; "
          f"spot check {'OK' if ok else 'FAILED'}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
