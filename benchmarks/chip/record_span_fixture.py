"""Record the small trace with the program's own ``repro.*`` spans that
``test_chip_bench_spans.py`` reads: the job ``record_fixture.py`` traces
(one FedAsync CNN job, 8 clients, 32 global iterations), written beside
its fixture.  Run on a TPU:

    python3 benchmarks/chip/record_span_fixture.py
"""
import sys

import record_fixture

if __name__ == "__main__":
    record_fixture.FIXTURE = (record_fixture.HERE / "fixtures"
                              / "cnn_fedasync_spans.xplane.pb")
    sys.exit(record_fixture.main())
