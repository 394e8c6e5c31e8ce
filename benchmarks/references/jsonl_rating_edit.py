"""Reference for ``-o json-lines`` over a layer whose edit commit rewrote
``rating``: the builder knows which pks it edited and both values."""

import json

import numpy as np


def check(output, info):
    """-> {check name: bool}: the features named are the builder's edited
    pks, each once and in order, and every line carries the builder's two
    rating values and an unchanged geometry."""
    pks, values_ok = [], True
    for line in output.splitlines():
        obj = json.loads(line)
        if obj.get("type") != "feature":
            continue
        old, new = obj["change"]["-"], obj["change"]["+"]
        pks.append(new["fid"])
        values_ok = values_ok and (
            old["fid"] == new["fid"]
            and old["rating"] == new["fid"] / 2.0
            and new["rating"] == float(new["fid"])
            and old["geom"] == new["geom"]
        )
    return {
        "names_the_edited_pks": np.array_equal(
            np.asarray(pks, dtype=np.int64), info["edit_pks"]
        ),
        "values_are_the_builders": values_ok,
    }
