"""Plain float32 references, one file per model family. A configuration
names its family's file (``"reference": "benchmark/reference/<family>.py"``)
and ``harness.load_family`` loads it from there: no list of families is
kept anywhere. What a family's file exports is in ``harness.load_family``."""
