"""Work of one random-forest inference launch, from shapes alone.

Operations: one per node visit (rows x trees x depth) and one per leaf
added (rows x trees). Bytes: the forest tables once (a feature index and
a threshold per internal node, 4 bytes each, and a 4-byte value per
leaf), the float32 feature rows and the float32 outputs. The same count
whatever implements the traversal (one-hot selects, gathers).
"""


def per_launch(rows: int, trees: int, depth: int, features: int) -> dict:
    """{"ops", "bytes"} of one launch over `rows` feature rows."""
    nodes = 2 ** depth - 1
    leaves = 2 ** depth
    tables = trees * (nodes * (4 + 4) + leaves * 4)
    return {"ops": rows * trees * depth + rows * trees,
            "bytes": tables + rows * features * 4 + rows * 4}
