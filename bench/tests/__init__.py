"""The benchmark's own tests (``bench/tests/conftest.py`` says how to run
them). Importing the package registers the tiny cells kept beside
``tiny``, before any test module reads ``tiny.CELLS``."""

from bench.tests import tiny_deepseek

tiny_deepseek.register()
