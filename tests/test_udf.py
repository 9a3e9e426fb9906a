"""UDF tier tests, mirroring the reference's extension coverage
(datax-udf-samples + ExtendedUDFHandler/JarUDFHandler registration):
jax scalar UDFs in queries, custom aggregates under GROUP BY, the
Pallas kernel escape hatch, conf-driven loading, interval refresh, and
the external-function output tier."""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax.numpy as jnp
import numpy as np
import pytest

from data_accelerator_tpu.compile.planner import TableData
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.processor import FlowProcessor
from data_accelerator_tpu.udf import JaxUdf, JaxUdaf, PallasUdf, load_udfs_from_conf
from data_accelerator_tpu.udf.samples import anomalyscore, lastabove, scaleby

SCHEMA = json.dumps({
    "type": "struct",
    "fields": [
        {"name": "deviceId", "type": "long", "nullable": False,
         "metadata": {"allowedValues": [1, 2, 3]}},
        {"name": "temperature", "type": "double", "nullable": False,
         "metadata": {"minValue": 0, "maxValue": 100}},
        {"name": "ts", "type": "long", "nullable": False,
         "metadata": {"allowedValues": [1, 2, 3, 4]}},
    ],
})


def make_proc(transform, udfs, capacity=64, outputs=None):
    conf = SettingDictionary({
        "datax.job.name": "UdfTest",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.process.transform": transform,
        "datax.job.process.projection": "Raw.*",
    })
    return FlowProcessor(
        conf, udfs=udfs, batch_capacity=capacity, output_datasets=outputs
    )


def feed(proc, device_ids, temps, tss):
    cap = proc.batch_capacity
    n = len(device_ids)
    cols = {
        "deviceId": np.zeros(cap, np.int32),
        "temperature": np.zeros(cap, np.float32),
        "ts": np.zeros(cap, np.int32),
    }
    cols["deviceId"][:n] = device_ids
    cols["temperature"][:n] = temps
    cols["ts"][:n] = tss
    raw = proc.encode_columns(cols, n)
    return proc.process_batch(raw, batch_time_ms=1_700_000_000_000)


class TestJaxUdf:
    def test_scalar_udf_in_query(self):
        double_it = JaxUdf("doubleit", lambda x: x.astype(jnp.float32) * 2.0,
                           out_type="double")
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT deviceId, doubleit(temperature) AS t2 "
            "FROM DataXProcessedInput",
            {"doubleit": double_it},
            outputs=["T"],
        )
        datasets, _ = feed(proc, [1, 2], [10.0, 20.5], [1, 2])
        assert [r["t2"] for r in datasets["T"]] == [20.0, 41.0]

    def test_udf_in_where(self):
        hot = JaxUdf("ishot", lambda x: x > 50.0, out_type="boolean")
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT deviceId FROM DataXProcessedInput "
            "WHERE ishot(temperature)",
            {"ishot": hot},
            outputs=["T"],
        )
        datasets, _ = feed(proc, [1, 2, 3], [80.0, 20.0, 60.0], [1, 2, 3])
        assert [r["deviceId"] for r in datasets["T"]] == [1, 3]

    def test_sample_hello_hoststr(self):
        from data_accelerator_tpu.udf.samples import HelloWorldUdf

        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT hello(deviceId) AS greet FROM DataXProcessedInput",
            {"hello": HelloWorldUdf()},
            outputs=["T"],
        )
        datasets, _ = feed(proc, [7], [1.0], [1])
        assert datasets["T"][0]["greet"] == "Hello 7"

    def test_interval_refresh_hook_called(self):
        calls = []
        u = JaxUdf("noop", lambda x: x, out_type="double",
                   on_interval=lambda ts: (calls.append(ts), False)[1])
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT noop(temperature) AS t FROM DataXProcessedInput",
            {"noop": u},
            outputs=["T"],
        )
        feed(proc, [1], [1.0], [1])
        feed(proc, [1], [1.0], [1])
        assert len(calls) == 2

    def test_interval_state_change_retraces_step(self):
        """A True on_interval must re-trace the jitted step so new
        captured state takes effect (DynamicUDF refresh semantics)."""
        state = {"factor": 1.0, "pending": False}

        def refresh(ts):
            if state["pending"]:
                state["factor"] = 10.0
                state["pending"] = False
                return True
            return False

        u = JaxUdf("dynscale",
                   lambda x: x.astype(jnp.float32) * state["factor"],
                   out_type="double", on_interval=refresh)
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT dynscale(temperature) AS s FROM DataXProcessedInput",
            {"dynscale": u},
            outputs=["T"],
        )
        d1, _ = feed(proc, [1], [3.0], [1])
        assert d1["T"][0]["s"] == 3.0
        state["pending"] = True  # next interval flips the factor
        d2, _ = feed(proc, [1], [3.0], [1])
        assert d2["T"][0]["s"] == 30.0

    def test_scaleby_sample(self):
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT scaleby(temperature) AS s FROM DataXProcessedInput",
            {"scaleby": scaleby()},
            outputs=["T"],
        )
        datasets, _ = feed(proc, [1], [21.0], [1])
        assert datasets["T"][0]["s"] == 42.0


class TestJaxUdaf:
    def test_custom_aggregate_in_groupby(self):
        def reduce(arg_arrays, seg, capacity, valid_s):
            from data_accelerator_tpu.ops.groupby import segment_aggregate

            vals = arg_arrays[0].astype(jnp.float32)
            sq = jnp.where(valid_s, vals * vals, jnp.zeros_like(vals))
            return segment_aggregate(sq, seg, capacity, "sum", valid_s)

        sumsq = JaxUdaf("sumsq", reduce, out_type="double")
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT deviceId, sumsq(temperature) AS ss "
            "FROM DataXProcessedInput GROUP BY deviceId",
            {"sumsq": sumsq},
            outputs=["T"],
        )
        datasets, _ = feed(proc, [1, 1, 2], [3.0, 4.0, 5.0], [1, 2, 3])
        got = {r["deviceId"]: r["ss"] for r in datasets["T"]}
        assert got == {1: 25.0, 2: 25.0}

    def test_lastabove_sample(self):
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT deviceId, lastabove(temperature, ts) AS last "
            "FROM DataXProcessedInput GROUP BY deviceId",
            {"lastabove": lastabove(threshold=10.0)},
            outputs=["T"],
        )
        # device 1: values 30 (ts1), 50 (ts3), 5 (ts4): last >10 is 50@ts3
        datasets, _ = feed(
            proc, [1, 1, 1, 2], [30.0, 50.0, 5.0, 7.0], [1, 3, 4, 2]
        )
        got = {r["deviceId"]: r["last"] for r in datasets["T"]}
        assert got[1] == 50.0
        assert got[2] == 0.0  # nothing above threshold

    def test_udaf_without_groupby_rejected(self):
        from data_accelerator_tpu.core.config import EngineException

        with pytest.raises(EngineException):
            make_proc(
                "--DataXQuery--\n"
                "T = SELECT lastabove(temperature, ts) AS x "
                "FROM DataXProcessedInput",
                {"lastabove": lastabove()},
                outputs=["T"],
            )


class TestPallasUdf:
    def test_pallas_kernel_runs(self):
        proc = make_proc(
            "--DataXQuery--\n"
            "T = SELECT deviceId, anomalyscore(temperature, deviceId) AS a "
            "FROM DataXProcessedInput",
            {"anomalyscore": anomalyscore(interpret=True)},
            outputs=["T"],
        )
        datasets, _ = feed(proc, [1, 2], [1.0, 100.0], [1, 2])
        rows = datasets["T"]
        # sigmoid(0)=0.5 at x==mu; saturates toward 1 as |x-mu| grows
        assert all(0.5 <= r["a"] <= 1.0 for r in rows)
        assert rows[1]["a"] > rows[0]["a"]

    def test_default_is_the_compiled_kernel_never_the_interpreter(self):
        """interpret is an explicit argument: on a host without a TPU
        the default (Mosaic) build fails instead of quietly switching
        to the interpreter."""
        udf = anomalyscore()
        assert udf.interpret is False
        with pytest.raises(ValueError, match="interpret mode"):
            udf.fn(jnp.zeros(8), jnp.zeros(8, jnp.int32))

    @pytest.mark.parametrize("n", [3, 4096, 5000, 40000])
    def test_ragged_and_multi_block_sizes_match_the_formula(self, n):
        """Rows are padded to whole (32, 128)-tiled blocks and sliced
        back: any batch size, one block or many, gives the formula's
        value for every row."""
        rng = np.random.RandomState(n)
        x = rng.uniform(0, 100, n).astype(np.float32)
        mu = rng.randint(1, 9, n).astype(np.int32)
        udf = anomalyscore(interpret=True)
        udf.block_rows = 8192
        got = np.asarray(udf.fn(jnp.asarray(x), jnp.asarray(mu)))
        want = 1.0 / (1.0 + np.exp(-(np.abs(x - mu) / (1.0 + np.abs(mu)))))
        assert got.shape == (n,)
        np.testing.assert_allclose(got, want, rtol=1e-6)


class TestConfLoading:
    def test_load_from_conf_namespace(self):
        d = SettingDictionary({
            "datax.job.process.jar.udf.anomalyscore.class":
                "data_accelerator_tpu.udf.samples:anomalyscore",
            "datax.job.process.jar.udaf.lastabove.class":
                "data_accelerator_tpu.udf.samples:lastabove",
        })
        udfs = load_udfs_from_conf(d)
        assert set(udfs) == {"anomalyscore", "lastabove"}
        assert udfs["lastabove"].is_aggregate

    def test_processor_loads_conf_udfs(self):
        conf = SettingDictionary({
            "datax.job.name": "ConfUdf",
            "datax.job.input.default.inputtype": "local",
            "datax.job.input.default.blobschemafile": SCHEMA,
            "datax.job.process.transform": (
                "--DataXQuery--\n"
                "T = SELECT scaleby(temperature) AS a "
                "FROM DataXProcessedInput"
            ),
            "datax.job.process.projection": "Raw.*",
            "datax.job.process.jar.udf.scaleby.class":
                "data_accelerator_tpu.udf.samples:scaleby",
        })
        proc = FlowProcessor(conf, batch_capacity=64, output_datasets=["T"])
        datasets, _ = feed(proc, [1], [50.0], [1])
        assert datasets["T"][0]["a"] == 100.0

    def test_class_path_instantiated(self):
        """A class (not factory) conf target must be instantiated."""
        d = SettingDictionary({
            "datax.job.process.jar.udf.hello.class":
                "data_accelerator_tpu.udf.samples:HelloWorldUdf",
        })
        udfs = load_udfs_from_conf(d)
        from data_accelerator_tpu.udf.samples import HelloWorldUdf

        assert isinstance(udfs["hello"], HelloWorldUdf)

    def test_bad_class_path_raises(self):
        from data_accelerator_tpu.core.config import EngineException

        d = SettingDictionary({
            "datax.job.process.jar.udf.x.class": "no.such.module:thing",
        })
        with pytest.raises(EngineException):
            load_udfs_from_conf(d)

    def test_duplicate_name_across_tiers_rejected(self):
        """Satellite: a name declared in BOTH the udf and udaf tiers
        used to silently last-win (the udaf shadowed the udf); now the
        loader rejects it with a typed EngineException."""
        from data_accelerator_tpu.core.config import EngineException

        d = SettingDictionary({
            "datax.job.process.jar.udf.lastabove.class":
                "data_accelerator_tpu.udf.samples:scaleby",
            "datax.job.process.jar.udaf.lastabove.class":
                "data_accelerator_tpu.udf.samples:lastabove",
        })
        with pytest.raises(EngineException, match="duplicate UDF name"):
            load_udfs_from_conf(d)

    def test_builtin_shadowing_rejected(self):
        """Satellite: a UDF named like an engine builtin (CONCAT, AVG,
        ...) would never be called — the compiler resolves builtins
        first — so registration fails instead of silently no-opping."""
        from data_accelerator_tpu.core.config import EngineException

        d = SettingDictionary({
            "datax.job.process.jar.udf.concat.class":
                "data_accelerator_tpu.udf.samples:scaleby",
        })
        with pytest.raises(EngineException, match="shadows the engine builtin"):
            load_udfs_from_conf(d)
        d2 = SettingDictionary({
            "datax.job.process.jar.udaf.avg.class":
                "data_accelerator_tpu.udf.samples:lastabove",
        })
        with pytest.raises(EngineException, match="shadows the engine builtin"):
            load_udfs_from_conf(d2)


class TestExternalFunctionSink:
    def test_rows_posted_per_event(self):
        from data_accelerator_tpu.runtime.sinks import ExternalFunctionSink

        received = []

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                received.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

            def log_message(self, *a):
                pass

        srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            sink = ExternalFunctionSink(
                f"http://127.0.0.1:{srv.server_address[1]}",
                api="run", code="k1",
            )
            assert "run?code=k1" in sink.url
            n = sink.write("Alerts", [{"a": 1}, {"a": 2}], 0)
            assert n == 2
            assert received == [{"a": 1}, {"a": 2}]
        finally:
            srv.shutdown()
            srv.server_close()
