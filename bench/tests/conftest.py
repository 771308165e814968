"""Keeps a test run from rewriting the example database that git tracks.

Hypothesis replays the examples saved under ``.hypothesis/examples``,
deletes each one that no longer fails, and saves new ones.  That directory
is committed, so every run of the suite would change tracked files.  Any
session that collects this directory therefore loads a profile, on top of
whichever is active, whose database is a read-only view of the same
directory: every saved example is still replayed, and nothing is written.
"""
import os

try:
    from hypothesis import settings
    from hypothesis.database import (DirectoryBasedExampleDatabase,
                                     ReadOnlyDatabase)
except ImportError:   # hypothesis is an optional dev dependency
    settings = None

if settings is not None:
    _ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    settings.register_profile(
        "tracked-examples-read-only",
        database=ReadOnlyDatabase(DirectoryBasedExampleDatabase(
            os.path.join(_ROOT, ".hypothesis", "examples"))))
    settings.load_profile("tracked-examples-read-only")
