"""Serving scenario: continuous batching + ReuseSense decode on a reduced
Mixtral, with measured sensor telemetry (the live Fig.-12 analogue): a
per-request `SensorReport rid=... slot=... steps=... hit_rate=...` line is
printed at each slot retirement, and the full per-site report at the end.

    PYTHONPATH=src python examples/serve_reuse.py

This is a thin driver over the production CLI path:
    python -m repro.launch.serve --arch mixtral-8x7b --reduced --reuse
"""

import sys

sys.path.insert(0, "src")

from repro.launch import serve


def main():
    serve.main([
        "--arch", "mixtral-8x7b", "--reduced",
        "--requests", "8", "--batch-slots", "4",
        "--prompt-len", "24", "--cache-len", "96",
        "--max-new", "12", "--reuse",
    ])


if __name__ == "__main__":
    main()
