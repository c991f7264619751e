"""solve_s: host seconds of the public ``falkon_solve``, compiled ahead in
set-up and run once on the traced fit's state (centers, preconditioner)."""


def read(obs):
    return obs.get("solve_s")
