"""Program-side builders of each model family the benchmark serves, found
by the ``family`` of a configuration file."""
