"""data_accelerator_tpu — a TPU-native streaming analytics framework.

A ground-up rebuild of the capabilities of Microsoft Data Accelerator
(reference: itshawi/data-accelerator): self-service streaming "Flows"
(input stream -> normalization/projection -> DataXQuery SQL + no-code rules
with time windows, accumulators and UDFs -> sinks + live metrics), compiled
to XLA kernels on TPU instead of Spark jobs on a JVM cluster.

Layer map (vs. reference layers, see SURVEY.md):
- ``core``      columnar batches, schemas, flat ``datax.job.*`` config
                (reference: datax-core config/, Spark DataFrames)
- ``compile``   Flow compiler: DataXQuery parser, rules codegen, SQL subset
                planner, flow-JSON flattener
                (reference: datax.sql.TransformSQLParser, DataX.Flow.CodegenRules,
                DataX.Config flattener)
- ``ops``       jax/Pallas kernels: filter, hash-groupby, join, windowed
                aggregation (reference: delegated to Spark SQL execution)
- ``runtime``   micro-batch streaming host, sources, sinks, checkpointing,
                state tables (reference: datax-host host/, input/, sink/)
- ``parallel``  device-mesh sharding, ICI collectives in place of shuffle
                (reference: Spark partitioning + Netty shuffle)
- ``extension`` UDF tiers incl. the Pallas escape hatch
                (reference: datax.extension.DynamicUDF, JarUDF)
- ``serve``     control-plane REST, LiveQuery kernels, schema inference
                (reference: Services/DataX.Flow.*)
- ``obs``       metrics store + emission (reference: DataX.Metrics + Redis)
"""

__version__ = "0.1.0"
