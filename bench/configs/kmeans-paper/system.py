"""The system under test for kmeans-paper: the program's own streaming
k-means handler (``KMeans.make_processor(train=True)``, at the program's
default implementation) in the paper's edge-to-cloud pipeline."""
from benchlib.fleet import edge_to_cloud


def build(config, model_seed, probe):
    from repro.ml import KMeans
    m = config["model"]
    detector = KMeans(n_clusters=m["n_clusters"],
                      n_features=m["n_features"], seed=model_seed)
    return edge_to_cloud(
        config["fleet"],
        lambda params: detector.make_processor(params, train=True),
        probe.produce, probe.wrap)
