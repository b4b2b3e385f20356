"""The system under test for autoencoder-paper: the program's own
auto-encoder handler (``AutoEncoder.make_processor(train=True)``: score
with the held model, then 100 epochs of Adam on the message, then
publish) in the paper's edge-to-cloud pipeline.  The widths and epochs
are handed over by keyword; the rest of PyOD's settings are the
program's constants, which must read as the configuration states them."""
from benchlib.fleet import edge_to_cloud

# configuration key -> the program's constant of the same setting
SETTINGS = {"batch_size": "BATCH_SIZE", "dropout_rate": "DROPOUT_RATE",
            "l2_regularizer": "L2_REGULARIZER",
            "validation_size": "VALIDATION_SIZE",
            "contamination": "CONTAMINATION"}
OPTIMIZER = {"lr": "LR", "beta_1": "BETA_1", "beta_2": "BETA_2",
             "epsilon": "EPSILON"}


def build(config, model_seed, probe):
    from repro.ml import AutoEncoder, autoencoder
    m = config["model"]
    for part, names in ((m, SETTINGS), (config["optimizer"], OPTIMIZER)):
        for key, const in names.items():
            got = getattr(autoencoder, const)
            if got != part[key]:
                raise ValueError(f"the program's {const} is {got}, the "
                                 f"configuration states {key}={part[key]}")
    detector = AutoEncoder(n_features=m["n_features"],
                           hidden=tuple(m["hidden"]), epochs=m["epochs"],
                           seed=model_seed)
    return edge_to_cloud(
        config["fleet"],
        lambda params: detector.make_processor(params, train=True),
        probe.produce, probe.wrap)
