"""Sample-trace generator: ``python -m bucketeer_tpu.obs out.json``.

Runs one real (tiny) encode through the cross-request scheduler with
tracing on and writes the request's Chrome-trace JSON — the artifact
the CI ``obs`` job uploads so a reviewer can drop a real span tree
into chrome://tracing or ui.perfetto.dev without booting the server.
``--synthetic`` runs no JAX computation (a hand-built span tree), for
environments without a working backend.
"""
from __future__ import annotations

import json
import sys
import time


def _synthetic_spans():
    from . import request_context, span

    with request_context("sample-request"):
        with span("http.getImage", method="GET", path="/images/sample"):
            with span("decode.queue_wait"):
                time.sleep(0.002)
            with span("decode.read"):
                with span("decode.t2_parse"):
                    time.sleep(0.001)
                with span("decode.t1"):
                    time.sleep(0.003)
                with span("decode.device_inverse"):
                    time.sleep(0.001)


def _real_encode():
    import numpy as np

    from ..codec.encoder import EncodeParams
    from ..engine.scheduler import EncodeScheduler
    from . import request_context, span

    sched = EncodeScheduler(window_s=0.005)
    try:
        img = np.linspace(0, 255, 96 * 96 * 3).reshape(
            96, 96, 3).astype(np.uint8)
        with request_context("sample-request"):
            with span("http.loadImage", method="GET",
                      path="/images/sample/sample.tif"):
                sched.encode_jp2(img, 8, EncodeParams(
                    lossless=True, levels=2))
    finally:
        sched.close()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    synthetic = "--synthetic" in argv
    paths = [a for a in argv if not a.startswith("-")]
    if len(paths) != 1:
        print("usage: python -m bucketeer_tpu.obs [--synthetic] "
              "OUT.json", file=sys.stderr)
        return 2

    from . import Recorder, chrome_trace, install

    install(Recorder())
    try:
        if synthetic:
            _synthetic_spans()
        else:
            try:
                _real_encode()
            # Reported on stderr, then degraded — the artifact must
            # exist even where no backend does.
            except Exception as exc:  # graftlint: disable=swallowed-exception
                print(f"real encode unavailable ({exc}); "
                      "falling back to --synthetic", file=sys.stderr)
                _synthetic_spans()
        doc = chrome_trace("sample-request")
    finally:
        install(None)
    with open(paths[0], "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2)
    print(f"wrote {len(doc['traceEvents'])} trace event(s) to "
          f"{paths[0]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
