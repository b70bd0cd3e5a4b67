"""One file per model family, found by the ``family`` key of a configuration."""
