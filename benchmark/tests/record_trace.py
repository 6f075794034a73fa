"""One traced run of a cell that also keeps a short recorded stretch of its
profiler capture, for benchmark/tests/data/:

    python3 benchmark/tests/record_trace.py <out.json> --workload ... (run.py's arguments)
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

out_path = os.path.abspath(sys.argv[1])
reduce_profile = run.reduce_profile


def recording(capture):
    """The first capture of the run (the window's) is the one kept."""
    return reduce_profile(
        capture, None if os.path.exists(out_path) else out_path)


run.reduce_profile = recording
sys.exit(run.main(sys.argv[2:]))
