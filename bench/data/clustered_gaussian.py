"""clustered_gaussian: Gaussian clusters of unit variance around centres
drawn with standard deviation `spread` (parameters `n_clusters`, `spread`).

A copy of the program's `repro.data.synthetic.clustered_vectors`, kept here
so that no change to the program can change the benchmark's data.
"""
import numpy as np


def generate(n: int, d: int, params: dict, seed: int) -> np.ndarray:
    """(n, d) f32 drawn from the mixture the parameters name."""
    rng = np.random.default_rng(seed)
    centers = (rng.normal(size=(params["n_clusters"], d)).astype(np.float32)
               * params["spread"])
    assign = rng.integers(0, params["n_clusters"], n)
    return (centers[assign]
            + rng.normal(size=(n, d)).astype(np.float32)).astype(np.float32)
