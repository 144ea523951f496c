"""System modules: how a configuration builds, checks and references the program."""
