"""The benchmark's own tests (``chipbench/tests``), collected where tier-1
runs them. Each module here re-exports one file of ``chipbench/tests``
unchanged: the tests stay the benchmark's (fixtures are found from the
original files' ``__file__``), and one module a file spreads them over the
workers of ``--dist loadfile``."""
