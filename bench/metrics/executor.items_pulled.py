"""Mean list items pulled per answered query (the program's
``EngineResult.n_pulled``): the work Spec-QP's plan leaves the executor."""
import numpy as np


def read(run):
    pulled = [r.n_pulled for r in run.window.results if r is not None]
    return float(np.mean(pulled)) if pulled else None
