"""fit_minus_solve_s: the traced fit's host seconds minus ``solve_s`` on the
same state. That is center selection, the Gram, the factors and the wrap,
and also the host work of the fit's own solve that the ahead-compiled
``solve_s`` does not pay: its eager ``lax.scan`` calls lowered again and
loaded from the compile cache, and their dispatch."""


def read(obs):
    if "fit_s" not in obs or "solve_s" not in obs:
        return None
    return obs["fit_s"] - obs["solve_s"]
