"""On-chip benchmark of the tiled segmentation server.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the TPU it is started on and prints
one JSON result line last.  Cells, configurations, architectures and
metrics are found by name: ``workloads/<cell>.json``,
``configs/<config>.json``, ``archs/<arch>.py`` (a configuration's
``"arch"``) and ``metrics/<metric>.py``.
"""
