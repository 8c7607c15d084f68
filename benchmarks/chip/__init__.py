"""The chip benchmark: simulator sweeps and training steps as cells.

    python3 -m benchmarks.chip.run --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the repository root names every cell (a
configuration under a traffic mix), every end-to-end metric and every
per-layer metric. The harness finds each piece in a file of its own:

* ``configs/<config>.json`` -- the configuration as it is run, with its
  source, ``reduced`` and ``assumed`` keys; ``configs/<config>.py`` beside
  it is the plain reference that decides ``correct``;
* ``traffic/<mix>.json`` -- the parameters of a traffic mix, read by the
  driver its ``kind`` names (:mod:`.sweep` or :mod:`.train`);
* ``metrics/<metric>.py`` -- a reader with ``read(obs)`` that reduces the
  traced run's observation to one number, or ``None`` when it finds
  nothing to read.

Shared pieces: :mod:`.peaks` (peaks by ``device_kind``), :mod:`.flops`
(model operations per token), :mod:`.trace` (profiler trace to busy
time, idle share, top ops and gaps by host span), :mod:`.clock` (compile
events), :mod:`.device` (the chip check and peak memory).
"""
