"""beam.evals_per_query.batch: sum over count of the program's
``quiver_nav_evals`` histogram in the window (distance evaluations per
query; the traced run arms an ObsHub so that the program records it)."""


def read(rec):
    ev = rec["nav"].get("evals")
    if not ev or ev["count"] == 0:
        return None
    return ev["sum"] / ev["count"]
