"""TpuCodec gRPC sidecar — the codec service boundary (SURVEY §7 P2).

Serves Encode / ExtendAndRoot / Roots / Repair over whole squares so a Go
node can plug the TPU codec behind rsmt2d's pluggable `Codec` interface
(reference: pkg/da/data_availability_header.go:65-75,
pkg/appconsts/global_consts.go DefaultCodec) by generating a client from
service/tpu_codec.proto and dialing this server.

Backend order mirrors App._extend_and_hash: TPU (jax) > native C++ >
numpy reference — all byte-identical (the contract tests pin the DAH
through the service against the in-process path, and bench.py reports
the service round-trip overhead so the boundary's latency budget is an
explicit number, not a hope).

Run standalone:  python -m celestia_tpu.service.codec_service [--port N]
"""

from __future__ import annotations

import concurrent.futures
import logging
import random
import time

import grpc
import numpy as np

from celestia_tpu import faults, tracing
from celestia_tpu.appconsts import SHARE_SIZE
from celestia_tpu.service import wire

SERVICE_NAME = "celestia_tpu.codec.v1.TpuCodec"

log = logging.getLogger("celestia_tpu.codec_service")


class CodecBackend:
    """Dispatches to the fastest available implementation, degrading
    gracefully: a TPU-path failure falls back to the host path for that
    request (byte-identical DAH by construction — both paths are pinned
    against each other), and `tpu_strike_limit` CONSECUTIVE failures
    flip `use_tpu` off so a flaky device serves correct-but-slower
    instead of erroring on every call. Fallbacks and the flip are
    counted in telemetry.metrics (codec_tpu_fallback_total,
    codec_tpu_disabled_total)."""

    def __init__(self, use_tpu: bool | None = None,
                 tpu_strike_limit: int = 3):
        if use_tpu is None:
            use_tpu = self._tpu_available()
        self.use_tpu = use_tpu
        self.tpu_strike_limit = tpu_strike_limit
        self._tpu_strikes = 0

    def _tpu(self, op: str, fn, fallback):
        """Run the TPU path; on any runtime failure count a strike,
        serve the request from the host path, and after the strike
        limit degrade stickily to host-only."""
        from celestia_tpu.telemetry import metrics

        with tracing.span("codec.backend", op=op, backend="tpu") as bspan:
            try:
                out = fn()
            except Exception as e:  # noqa: BLE001 — any device failure degrades
                from celestia_tpu.da.repair import UnrepairableError

                if isinstance(e, (ValueError, UnrepairableError)):
                    # a data/shape condition, not a device fault: the host
                    # path would reject it identically — no strike, no retry
                    raise
                self._tpu_strikes += 1
                metrics.incr_counter("codec_tpu_fallback_total", op=op)
                log.warning(
                    "TPU %s failed (%s) — host fallback, strike %d/%d",
                    op, e, self._tpu_strikes, self.tpu_strike_limit,
                )
                if self._tpu_strikes >= self.tpu_strike_limit and self.use_tpu:
                    self.use_tpu = False
                    metrics.incr_counter("codec_tpu_disabled_total")
                    log.error(
                        "TPU path disabled after %d consecutive failures — "
                        "serving from the host backend", self._tpu_strikes,
                    )
                bspan.set(backend="host", degraded=True,
                          strikes=self._tpu_strikes,
                          disabled=not self.use_tpu,
                          cause=type(e).__name__)
                return fallback()
            self._tpu_strikes = 0  # only CONSECUTIVE failures degrade
            return out

    @staticmethod
    def _tpu_available() -> bool:
        from celestia_tpu.app.app import accelerator_available

        return accelerator_available()

    def _to_array(self, shares: bytes, width: int, share_size: int) -> np.ndarray:
        expect = width * width * share_size
        if len(shares) != expect:
            raise ValueError(
                f"share buffer is {len(shares)} bytes, expected {expect} "
                f"({width}x{width}x{share_size})"
            )
        return np.frombuffer(shares, dtype=np.uint8).reshape(
            width, width, share_size
        )

    def encode(self, k: int, share_size: int, shares: bytes) -> bytes:
        arr = self._to_array(shares, k, share_size)

        def host() -> bytes:
            from celestia_tpu import da

            eds = da.extend_shares(arr.reshape(k * k, share_size))
            return np.asarray(eds.data, dtype=np.uint8).tobytes()

        if self.use_tpu and share_size == SHARE_SIZE:
            def device() -> bytes:
                from celestia_tpu.ops import extend_tpu

                eds, _rows, _cols = extend_tpu.extend_roots_device(arr)
                return eds.tobytes()

            return self._tpu("encode", device, host)
        return host()

    def extend_and_root(self, k: int, share_size: int, shares: bytes):
        arr = self._to_array(shares, k, share_size)

        def host():
            from celestia_tpu import da

            eds = da.extend_shares(arr.reshape(k * k, share_size))
            return eds.row_roots(), eds.col_roots()

        if self.use_tpu and share_size == SHARE_SIZE:
            def device():
                from celestia_tpu.ops import extend_tpu

                _eds, rows, cols = extend_tpu.extend_roots_device(arr)
                return ([r.tobytes() for r in rows],
                        [c.tobytes() for c in cols])

            row_roots, col_roots = self._tpu("extend_and_root", device, host)
        else:
            row_roots, col_roots = host()
        from celestia_tpu.ops.nmt_host import merkle_root

        dah = merkle_root(row_roots + col_roots)
        return row_roots, col_roots, dah

    def roots(self, k: int, share_size: int, eds_bytes: bytes):
        from celestia_tpu import da
        from celestia_tpu.ops.nmt_host import merkle_root

        arr = self._to_array(eds_bytes, 2 * k, share_size)
        eds = da.ExtendedDataSquare(np.array(arr), k)
        row_roots, col_roots = eds.row_roots(), eds.col_roots()
        return row_roots, col_roots, merkle_root(row_roots + col_roots)

    def repair(self, k: int, share_size: int, eds_bytes: bytes,
               present: bytes) -> bytes:
        arr = self._to_array(eds_bytes, 2 * k, share_size)
        mask = np.frombuffer(present, dtype=np.uint8).reshape(2 * k, 2 * k) != 0

        def host() -> bytes:
            from celestia_tpu.da.repair import repair

            return repair(arr, mask).tobytes()

        if self.use_tpu and share_size == SHARE_SIZE:
            # same backend ordering as encode: the accelerated
            # host-planned/device-swept decode (bench config 4), byte-
            # exact vs the host path (tests pin all implementations)
            def device() -> bytes:
                from celestia_tpu.ops.repair_tpu import repair_tpu

                return repair_tpu(arr, mask).tobytes()

            return self._tpu("repair", device, host)
        return host()


def _handler(fn, req_cls, resp_marshal, method: str = ""):
    def handle(request_bytes, context):
        try:
            with tracing.span("codec.rpc", method=method,
                              request_bytes=len(request_bytes)):
                faults.fire("codec.backend")
                return resp_marshal(fn(req_cls.unmarshal(request_bytes)))
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        except (faults.DeviceUnavailable, faults.TransportFault) as e:
            # transient backend loss maps to UNAVAILABLE — the status a
            # well-behaved client retries (CodecClient._call does)
            context.abort(grpc.StatusCode.UNAVAILABLE, str(e))
        except Exception as e:  # noqa: BLE001 — surfaced as INTERNAL
            log.exception("codec RPC failed")
            context.abort(grpc.StatusCode.INTERNAL, str(e))

    return grpc.unary_unary_rpc_method_handler(
        handle,
        request_deserializer=lambda b: b,  # raw; decoded inside for abort()
        response_serializer=lambda b: b,
    )


class CodecServer:
    def __init__(self, port: int = 0, use_tpu: bool | None = None,
                 max_workers: int = 4):
        self.backend = CodecBackend(use_tpu)
        # squares are large: k=128 EDS is 32 MiB — lift the 4 MiB default
        opts = [
            ("grpc.max_receive_message_length", 64 * 1024 * 1024),
            ("grpc.max_send_message_length", 64 * 1024 * 1024),
        ]
        self.server = grpc.server(
            concurrent.futures.ThreadPoolExecutor(max_workers=max_workers),
            options=opts,
        )
        self.server.add_generic_rpc_handlers((self._service_handler(),))
        self.port = self.server.add_insecure_port(f"127.0.0.1:{port}")

    def _service_handler(self):
        b = self.backend

        def encode(req: wire.EncodeRequest) -> bytes:
            return wire.EdsResponse(b.encode(req.k, req.share_size, req.shares)).marshal()

        def extend_and_root(req: wire.EncodeRequest) -> bytes:
            rows, cols, dah = b.extend_and_root(req.k, req.share_size, req.shares)
            return wire.RootsResponse(rows, cols, dah).marshal()

        def roots(req: wire.EdsRequest) -> bytes:
            rows, cols, dah = b.roots(req.k, req.share_size, req.eds)
            return wire.RootsResponse(rows, cols, dah).marshal()

        def repair(req: wire.RepairRequest) -> bytes:
            return wire.EdsResponse(
                b.repair(req.k, req.share_size, req.eds, req.present)
            ).marshal()

        handlers = {
            "Encode": _handler(encode, wire.EncodeRequest, lambda x: x,
                               method="Encode"),
            "ExtendAndRoot": _handler(extend_and_root, wire.EncodeRequest,
                                      lambda x: x, method="ExtendAndRoot"),
            "Roots": _handler(roots, wire.EdsRequest, lambda x: x,
                              method="Roots"),
            "Repair": _handler(repair, wire.RepairRequest, lambda x: x,
                               method="Repair"),
        }
        return grpc.method_handlers_generic_handler(SERVICE_NAME, handlers)

    def start(self) -> None:
        self.server.start()

    def stop(self, grace: float = 0.5) -> None:
        self.server.stop(grace)


class CodecClient:
    """Python client over the same hand-rolled codecs (a Go client uses
    protoc-generated stubs from tpu_codec.proto instead).

    Every call carries a deadline (`timeout`, seconds) — a hung server
    yields DEADLINE_EXCEEDED instead of blocking forever — and
    UNAVAILABLE / DEADLINE_EXCEEDED statuses are retried `retries`
    times with exponential backoff + full jitter before the RpcError
    propagates."""

    _RETRY_CODES = (grpc.StatusCode.UNAVAILABLE,
                    grpc.StatusCode.DEADLINE_EXCEEDED)

    def __init__(self, target: str, timeout: float = 5.0,
                 retries: int = 2, backoff_base: float = 0.05):
        opts = [
            ("grpc.max_receive_message_length", 64 * 1024 * 1024),
            ("grpc.max_send_message_length", 64 * 1024 * 1024),
        ]
        self.channel = grpc.insecure_channel(target, options=opts)
        self.timeout = timeout
        self.retries = retries
        self.backoff_base = backoff_base

    def _call(self, method: str, request_bytes: bytes) -> bytes:
        from celestia_tpu.telemetry import metrics

        fn = self.channel.unary_unary(
            f"/{SERVICE_NAME}/{method}",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        last = None
        for attempt in range(self.retries + 1):
            with tracing.span("codec.call", method=method,
                              attempt=attempt) as cspan:
                try:
                    corrupt = faults.fire("codec.call", method=method)
                    out = fn(request_bytes, timeout=self.timeout)
                    return corrupt(out) if corrupt is not None else out
                except faults.TransportFault as e:
                    last, code = e, grpc.StatusCode.UNAVAILABLE
                except grpc.RpcError as e:
                    last, code = e, e.code()
                cspan.set(error=code.name)
            if code not in self._RETRY_CODES or attempt >= self.retries:
                raise last
            metrics.incr_counter("codec_call_retry_total", method=method)
            time.sleep(random.uniform(
                0.0, self.backoff_base * (2 ** attempt)
            ))
        raise last  # pragma: no cover — loop always returns or raises

    def encode(self, shares: np.ndarray) -> np.ndarray:
        k, _, share_size = shares.shape
        req = wire.EncodeRequest(k, share_size, np.ascontiguousarray(shares).tobytes())
        resp = wire.EdsResponse.unmarshal(self._call("Encode", req.marshal()))
        return np.frombuffer(resp.eds, dtype=np.uint8).reshape(
            2 * k, 2 * k, share_size
        )

    def extend_and_root(self, shares: np.ndarray):
        k, _, share_size = shares.shape
        req = wire.EncodeRequest(k, share_size, np.ascontiguousarray(shares).tobytes())
        resp = wire.RootsResponse.unmarshal(
            self._call("ExtendAndRoot", req.marshal())
        )
        return resp.row_roots, resp.col_roots, resp.dah_hash

    def roots(self, eds: np.ndarray):
        width, _, share_size = eds.shape
        req = wire.EdsRequest(width // 2, share_size,
                              np.ascontiguousarray(eds).tobytes())
        resp = wire.RootsResponse.unmarshal(self._call("Roots", req.marshal()))
        return resp.row_roots, resp.col_roots, resp.dah_hash

    def repair(self, eds: np.ndarray, present: np.ndarray) -> np.ndarray:
        width, _, share_size = eds.shape
        req = wire.RepairRequest(
            width // 2, share_size,
            np.ascontiguousarray(eds).tobytes(),
            np.ascontiguousarray(present.astype(np.uint8)).tobytes(),
        )
        resp = wire.EdsResponse.unmarshal(self._call("Repair", req.marshal()))
        return np.frombuffer(resp.eds, dtype=np.uint8).reshape(
            width, width, share_size
        )

    def close(self) -> None:
        self.channel.close()


def main(argv=None):
    import argparse
    import time

    parser = argparse.ArgumentParser(prog="tpu-codec-service")
    parser.add_argument("--port", type=int, default=9090)
    parser.add_argument("--cpu", action="store_true",
                        help="force the host backend (no TPU)")
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    server = CodecServer(port=args.port, use_tpu=False if args.cpu else None)
    server.start()
    log.info("TpuCodec service listening on 127.0.0.1:%d (tpu=%s)",
             server.port, server.backend.use_tpu)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
