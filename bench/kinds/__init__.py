"""Drivers, one per kind of cell (a configuration's ``kind``)."""
