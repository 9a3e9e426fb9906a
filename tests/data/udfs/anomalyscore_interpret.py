"""``anomalyscore`` built with the Pallas interpreter, for flows that a
test runs in a child process on a host without a TPU (conf class
``tests.data.udfs.anomalyscore_interpret:anomalyscore``). The shipped
factory (``data_accelerator_tpu.udf.samples:anomalyscore``) is always
the Mosaic build."""

from data_accelerator_tpu.udf.samples import anomalyscore as _compiled


def anomalyscore():
    return _compiled(interpret=True)
