"""Adaptive-serving scenario: the online control plane (repro.control) rides
the serving loop. Every 4 decode steps the controller retunes per-site
tunables from windowed live counters, adapts `max_active_k` budgets from the
measured overflow-fallback rate, and the learned admission predictor places
requests by per-session similarity estimated from retirement telemetry — no
offline record→fit→reload round trip. Watch for `ControlReport` lines (one
per decision) and the decision-journal summary at the end.

    PYTHONPATH=src python examples/serve_adaptive.py

This is a thin driver over the production CLI path:
    python -m repro.launch.serve --arch qwen3-32b --reduced --reuse \
        --control-every 4 --control-journal decisions.jsonl
"""

import sys
import tempfile

sys.path.insert(0, "src")

from repro.launch import serve


def main():
    journal = tempfile.NamedTemporaryFile(
        mode="w", suffix=".jsonl", prefix="decisions-", delete=False
    )
    serve.main([
        "--arch", "qwen3-32b", "--reduced",
        "--requests", "8", "--batch-slots", "4",
        "--prompt-len", "24", "--cache-len", "96",
        "--max-new", "16", "--reuse",
        "--control-every", "4", "--control-journal", journal.name,
    ])
    print(f"replay the run's decisions from {journal.name} with "
          f"repro.control.load_journal")


if __name__ == "__main__":
    main()
