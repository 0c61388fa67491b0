"""Offline replay/triage for the runtime driftwatch history ring.

``python -m tools.driftwatch`` (or the ``driftwatch`` console script)
reads the JSONL history that ``runtime/driftwatch.py`` appends every
cycle under ``<data_dir>/driftwatch/`` and re-classifies each cycle's
live telemetry against any baseline file — the triage artifact
ROADMAP item 1(c) asks for: after an incident you can replay the exact
telemetry the node saw, against the node's own sealed baseline or a
what-if baseline, without the node.
"""

from tools.driftwatch.cli import main  # noqa: F401
