"""The benchmark: harness, data and reducers (see ``chipbench/README.md``)."""
