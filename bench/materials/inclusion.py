"""inclusion: a spherical inclusion in a matrix, by element centroid.

``{"E_matrix", "E_inclusion", "nu_matrix", "nu_inclusion", "center",
"radius"}``."""


def fields(ref, mat):
    return ref.inclusion(mat["E_matrix"], mat["E_inclusion"],
                         mat["nu_matrix"], mat["nu_inclusion"],
                         mat["center"], mat["radius"])
