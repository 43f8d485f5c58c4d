"""Drivers, one per traffic ``kind``: each builds the system under test from
the configuration, runs the window, and checks what the window produced."""
