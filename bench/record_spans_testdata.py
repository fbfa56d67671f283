#!/usr/bin/env python3
"""Records the small device trace with the program's spans on the
profiler's clock (run on a TPU).

    python bench/record_spans_testdata.py

``record_testdata.py``'s recipe (two whole fits of a small hashed
corpus, ``RCCA_TRACE`` set in the traced window), written to
``bench/testdata/fit_spans/``.  With ``RCCA_TRACE`` set every program
span is also an ``rcca.<name>`` annotation in the ``.xplane.pb``, so
``bench/test_bench_launches.py`` can charge each device program to the
span that launched it.
"""

import os
import sys

import record_testdata

if __name__ == "__main__":
    record_testdata.OUT = os.path.join(record_testdata.BENCH, "testdata", "fit_spans")
    sys.exit(record_testdata.main())
