"""Work of one kmeans-paper message, counted from shapes.

One assign+update step over ``N`` points of ``F`` float32 features and
``K`` centroids needs at least:

* FLOPs: ``2NKF`` for the products ``x.c``, ``2NF`` for ``|x|^2``,
  ``2KF`` for ``|c|^2``, ``2NK`` to combine them, ``NF`` for the member
  sums and ``4KF`` for the centroid move;
* bytes: the points read once (``4NF``), the centroids and counts read
  and written (``8KF + 8K``), and a score and an id written per point
  (``8N``).

At 10,000 x 32 x 25 that is 1.75e7 FLOPs against 1.37e6 bytes, so the
step is bound by memory on any chip whose FLOPs per byte exceed 13.
"""


def step(config):
    """``(flops, bytes)`` of one assign+update step."""
    n = config["pool"]["n_points"]
    f = config["model"]["n_features"]
    k = config["model"]["n_clusters"]
    flops = 2 * n * k * f + 2 * n * f + 2 * k * f + 2 * n * k + n * f \
        + 4 * k * f
    nbytes = 4 * n * f + 8 * k * f + 8 * k + 8 * n
    return float(flops), float(nbytes)


def message(config):
    """``(flops, bytes)`` of the whole handler for one message."""
    return step(config)
