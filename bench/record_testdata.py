#!/usr/bin/env python3
"""Records the small device trace the unit tests reduce (run on a TPU).

    python bench/record_testdata.py

Two whole fits of a small hashed corpus (d = 4096, 4 chunks of 256 rows,
k~ = 64) through ``randomized_cca_iterator``, traced with the same
profiler options and the same host annotations as ``bench/run.py``,
with the program's ``RCCA_TRACE`` spans beside it.  Writes
``bench/testdata/fit_small/`` (the ``.xplane.pb`` and ``rcca.jsonl``).
"""

import glob
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(BENCH, "testdata", "fit_small")


def main() -> int:
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    sys.path.insert(0, BENCH)
    import jax

    import corpora
    from repro.core.rcca import RCCAConfig, randomized_cca_iterator
    from repro.data import HashingFeaturizer

    if jax.devices()[0].platform != "tpu":
        print("record_testdata: needs a TPU", file=sys.stderr)
        return 2
    d, chunk, nc = 4096, 256, 4
    docs_a, docs_b = corpora.paired_docs(chunk * nc, 7)
    fa, fb = HashingFeaturizer(d, 11), HashingFeaturizer(d, 12)
    cfg = RCCAConfig(k=16, p=48, q=1, nu=0.01)
    key = corpora.jax_key(7, 0)

    def source():
        for i in range(nc):
            with jax.profiler.TraceAnnotation("bench.featurize"):
                rows = slice(i * chunk, (i + 1) * chunk)
                yield fa.featurize_batch(docs_a[rows]), fb.featurize_batch(docs_b[rows])

    def fit():
        with jax.profiler.TraceAnnotation("bench.fit"):
            jax.block_until_ready(randomized_cca_iterator(source, d, d, cfg, key, n_chunks=nc).Xa)

    fit()  # compile outside the trace
    tmp = os.path.join(OUT, "tmp")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(tmp)
    os.environ["RCCA_TRACE"] = tmp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        fit()
        fit()
    jax.profiler.stop_trace()
    del os.environ["RCCA_TRACE"]
    (pb,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.move(pb, os.path.join(OUT, "trace.xplane.pb"))
    with open(os.path.join(OUT, "rcca.jsonl"), "w") as out:
        for fp in sorted(glob.glob(os.path.join(tmp, "*.jsonl"))):
            with open(fp) as f:
                out.write(f.read())
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
