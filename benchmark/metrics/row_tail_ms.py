"""Device time of the two tail programs an adoption of a cached prefix:
the self time under the program's ``row_tail`` scope (``row_tail/export``:
a tail copied out of a batch row's window slabs; ``row_tail/import``: one
copied into a new row's ring) over the runs of the import program in the
first capture — what the device pays so that a window family adopts a
prefix hit instead of prefilling it. By the scope, so whatever
implements the copies. A program without the scope (another family; the
parent of the PR that added it), or a capture in which nothing was
adopted, gives nothing."""
from benchmark.harness.scopes import of_run, under


def read(run):
    red = of_run(run)
    if red is None:
        return None
    adoptions = sum(p["runs"] for p in red["programs"].values()
                    if any(k.split("/")[:2] == ["row_tail", "import"]
                           for k in p["paths"]))
    return under(red, "row_tail") / adoptions * 1e3 if adoptions else None
