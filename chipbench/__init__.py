"""chipbench: the benchmark of dynamo-tpu's served path on the TPU.

One run is one process: ``python3 -m chipbench --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` (see ``harness.py``). Everything that belongs
to one configuration, traffic mix, cell or per-layer metric is a data file
found by name (``configs/``, ``traffic/``, ``workloads/``, ``metrics/``);
readers, length distributions and reference architectures are modules
found by name (``readers/``, ``distributions/``, ``reference/``). This
package imports nothing heavy: ``loadgen`` must stay free of JAX.
"""
