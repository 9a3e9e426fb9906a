"""User-defined function tiers.

reference: the extension API surface —
- ``DynamicUDF.Generator0..3`` + per-batch refresh
  (datax-core/.../extension/DynamicUDF.scala:32-45,
  ExtendedUDFHandler.scala:23-112) -> ``JaxUdf`` with ``on_interval``.
- plain JAR UDFs / UDAFs loaded by reflection
  (JarUDFHandler.scala:13-100, SparkJarLoader.scala:24-165) ->
  ``load_udfs_from_conf`` importing ``module:attr`` python paths from the
  same ``datax.job.process.jar.udf.<name>.*`` conf namespace.
- custom aggregates (UserDefinedAggregateFunction) -> ``JaxUdaf`` with a
  segment-reduce over sorted groups.
- the Scala-tier escape hatch for custom kernels -> ``PallasUdf``
  (TPU Pallas kernel, compiled by Mosaic; the interpreter only when a
  test asks for it).
- AzureFunctionHandler's per-row external calls -> the
  ``externalfn`` sink kind (runtime/sinks.py), keeping network I/O out
  of the compiled graph by design.
"""

from .api import (
    JaxUdf,
    JaxUdaf,
    PallasUdf,
    UdfRegistry,
    load_udfs_from_conf,
)

__all__ = [
    "JaxUdf",
    "JaxUdaf",
    "PallasUdf",
    "UdfRegistry",
    "load_udfs_from_conf",
]
