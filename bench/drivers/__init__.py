"""Drivers, one per kind of configuration, found by the configuration's
``driver`` key."""
