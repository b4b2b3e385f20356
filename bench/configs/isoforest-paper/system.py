"""The system under test for isoforest-paper: the program's own isolation
forest handler (``IsolationForest.make_processor(train=True)``, a refit on
every message) in the paper's edge-to-cloud pipeline."""
from benchlib.fleet import edge_to_cloud


def build(config, model_seed, probe):
    from repro.ml import IsolationForest
    m = config["model"]
    detector = IsolationForest(n_trees=m["n_trees"], psi=m["psi"],
                               seed=model_seed)
    return edge_to_cloud(
        config["fleet"],
        lambda params: detector.make_processor(params, train=True),
        probe.produce, probe.wrap)
