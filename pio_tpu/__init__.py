"""pio_tpu — a TPU-native machine-learning server.

A from-scratch rebuild of the capabilities of Apache PredictionIO
(reference: TharinduDG/incubator-predictionio) on a JAX/XLA substrate:

- ``pio_tpu.data``       — event data model (Event, DataMap, PropertyMap, BiMap)
                           [ref: data/src/main/scala/o/a/p/data/storage/Event.scala etc.]
- ``pio_tpu.storage``    — storage SPI + backends (memory, SQLite, Parquet)
                           [ref: data/.../storage/Storage.scala + storage/* subprojects]
- ``pio_tpu.server``     — Event Server + per-engine Query Server (HTTP)
                           [ref: data/.../api/EventServer.scala, core/.../workflow/CreateServer.scala]
- ``pio_tpu.controller`` — DASE framework: DataSource, Preparator, Algorithm,
                           Serving, Evaluation/Metric [ref: core/.../controller/*]
- ``pio_tpu.workflow``   — train/eval/deploy workflow + engine registry
                           [ref: core/.../workflow/CreateWorkflow.scala, CoreWorkflow.scala]
- ``pio_tpu.models``     — JAX/TPU algorithm implementations (ALS, LogReg, ...)
                           replacing Spark MLlib
- ``pio_tpu.ops``        — Pallas kernels and TPU-friendly primitive ops
- ``pio_tpu.parallel``   — mesh / sharding / collective helpers replacing Spark
                           shuffle + treeAggregate
- ``pio_tpu.templates``  — bundled engines (recommendation, classification,
                           similar-product, e-commerce, text classification,
                           two-tower, sequence) [ref: examples/scala-parallel-*]
- ``pio_tpu.native``     — C++ runtime components (event-log storage engine,
                           ALS edge sort), built with g++ on first use
- ``pio_tpu.tools``      — the ``pio`` CLI equivalent

Where the reference dispatches work to Spark executors, this package runs
sharded JAX programs over a ``jax.sharding.Mesh``; XLA collectives over
ICI/DCN replace Spark shuffles and tree-aggregations.
"""

import time as _time

#: when this package was first imported, on ``pio_tpu.obs.monotonic_s``'s
#: clock: the process timeline's ``pio_tpu_imported`` mark
#: (``pio_tpu/obs/tracing.py``). Read before anything else is imported, so
#: that the package's own modules stand after the mark.
IMPORTED_AT = _time.perf_counter()

__version__ = "0.1.0"
