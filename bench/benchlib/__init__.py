"""The benchmark's yardstick: discovery of cells by name, traffic
generation, the message pool, the measured window, the trace reduction and
the arithmetic of the metrics.  Nothing here belongs to one
configuration, one traffic mix or one metric: those live in
``bench/configs/<config>/``, ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py``."""
