# Fixture snippets for gofrlint's unit tests (tests/test_analysis.py).
# These files are PARSED by the analyzer, never imported or executed —
# each <rule>_bad.py seeds known violations at known lines, each
# <rule>_good.py is the clean twin. Not linted by CI's repo run
# (scripts/lint.py gofr_tpu/ scripts/ chip_smoke.py excludes tests/).
