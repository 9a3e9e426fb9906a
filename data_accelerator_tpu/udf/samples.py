"""Sample UDFs: one per extension tier.

reference: datax-udf-samples/.../{udf/UdfHelloWorld,
udaf/UdafLastThreshold,dynamicudf/DynamicUdfHelloWorld,
normalizer/RemoveInvalidChars}.scala — the reference implementations of
all four extension interfaces, used by its tests and docs. These are the
conf-loadable equivalents (class = data_accelerator_tpu.udf.samples:<attr>).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..compile.exprs import HostStr, is_device
from ..core.config import EngineException
from .api import JaxUdaf, JaxUdf, PallasUdf


class HelloWorldUdf:
    """String-tier sample: ``hello(name)`` -> "Hello <name>".

    reference: UdfHelloWorld.scala — returns a device-deferred string
    template (strings materialize at the sink boundary, so arbitrary
    string construction stays off the device hot path).
    """

    name = "hello"
    is_aggregate = False

    def on_interval(self, batch_time_ms: int) -> bool:
        return False

    def compile_call(self, compiler, e):
        if len(e.args) != 1:
            raise EngineException("hello() takes one argument")
        arg = compiler.compile(e.args[0])
        if not is_device(arg):
            raise EngineException("hello() requires a device argument")
        return HostStr(parts=["Hello ", arg], deps=arg.deps)


def _scale_udf() -> JaxUdf:
    """Dynamic-tier sample: ``scaleby(x)`` multiplies by a factor that
    refreshes per interval (DynamicUdfHelloWorld.scala semantics: the
    generator's initialization captures state refreshed by onInterval)."""
    state = {"factor": 2.0, "refreshes": 0}

    def refresh(batch_time_ms: int) -> bool:
        state["refreshes"] += 1
        return False  # factor stable; flip to True when state changes

    return JaxUdf(
        "scaleby",
        lambda x: x.astype(jnp.float32) * state["factor"],
        out_type="double",
        on_interval=refresh,
    )


scaleby = _scale_udf


def _last_over_threshold(threshold: float = 0.0) -> JaxUdaf:
    """UDAF sample: latest value (by event time) above a threshold within
    each group. reference: UdafLastThreshold.scala:12-58 (stateful
    last-value-by-time aggregate)."""

    def reduce(arg_arrays, seg, capacity, valid_s):
        from ..ops.groupby import segment_aggregate

        value, ts = arg_arrays[0], arg_arrays[1]
        ok = valid_s & (value > threshold)
        neg = jnp.iinfo(jnp.int32).min
        ts_ok = jnp.where(ok, ts.astype(jnp.int32), neg)
        max_ts = segment_aggregate(ts_ok, seg, capacity, "max", valid_s)
        at_max = ok & (ts.astype(jnp.int32) == max_ts[jnp.clip(seg, 0, capacity - 1)])
        v = jnp.where(at_max, value.astype(jnp.float32), -jnp.inf)
        out = segment_aggregate(v, seg, capacity, "max", valid_s)
        return jnp.where(jnp.isfinite(out), out, jnp.zeros_like(out))

    return JaxUdaf("lastabove", reduce, out_type="double")


lastabove = _last_over_threshold


def _anomaly_kernel(x_ref, mu_ref, o_ref):
    """Pallas-tier sample: per-row anomaly score
    ``sigmoid(|x - mu| / (1 + |mu|))`` — an elementwise VPU kernel
    standing in for the reference's custom-Scala scoring UDFs."""
    x = x_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    d = jnp.abs(x - mu) / (1.0 + jnp.abs(mu))
    o_ref[...] = 1.0 / (1.0 + jnp.exp(-d))


def anomalyscore(interpret: bool = False) -> PallasUdf:
    return PallasUdf(
        "anomalyscore", _anomaly_kernel, out_type="double",
        out_dtype=jnp.float32, interpret=interpret,
    )


def remove_invalid_chars(raw: str) -> str:
    """Normalizer-tier sample: strip control chars from raw event text
    before JSON parse. reference: RemoveInvalidChars.scala
    (StringNormalizer trait)."""
    return "".join(ch for ch in raw if ch >= " " or ch in "\t")
