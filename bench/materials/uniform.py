"""uniform: one isotropic material, ``{"E": .., "nu": ..}``."""


def fields(ref, mat):
    return float(mat["E"]), float(mat["nu"])
