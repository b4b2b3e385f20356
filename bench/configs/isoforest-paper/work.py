"""Work of one isoforest-paper message, counted from shapes.

``T`` trees of depth ``D = ceil(log2 psi)`` over ``psi`` subsampled rows,
then ``N`` points of ``F`` float32 features scored through every tree.
Comparisons, minima and additions count as operations, and are held to
the bf16 peak: a generous bound, since none of them runs on the matrix
unit.

* The fit, per tree and level, reads one value per subsampled row and
  takes its node's minimum, maximum and count and its side of the split:
  ``4 psi`` operations and ``4 psi`` bytes; it writes the forest of
  ``2 ** (D + 1) - 1`` nodes of 13 bytes (feature, threshold, size and a
  leaf flag) per tree.
* The score, per point, tree and level, compares one value and adds one
  to the depth: ``2`` operations; it reads the points once (``4NF``) and
  the forest once, and writes one score per point (``4N``).

At 10,000 x 32 with 100 trees of 256 both are bound by memory.
"""
import math


def _shape(config):
    m, p = config["model"], config["pool"]
    depth = math.ceil(math.log2(m["psi"]))
    forest_bytes = m["n_trees"] * (2 ** (depth + 1) - 1) * 13
    return m["n_trees"], m["psi"], depth, p["n_points"], p["n_features"], \
        forest_bytes


def fit(config):
    """``(ops, bytes)`` of building the forest of one message."""
    trees, psi, depth, _, _, forest_bytes = _shape(config)
    return (float(4 * psi * depth * trees),
            float(4 * psi * depth * trees + forest_bytes))


def score(config):
    """``(ops, bytes)`` of scoring one message through the forest."""
    trees, _, depth, n, f, forest_bytes = _shape(config)
    return (float(2 * n * trees * depth),
            float(4 * n * f + forest_bytes + 4 * n))


def message(config):
    """``(ops, bytes)`` of the whole handler for one message."""
    (a, b), (c, d) = fit(config), score(config)
    return a + c, b + d
