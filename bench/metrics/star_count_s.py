"""Seconds per exact count of a counting job of IEP stars only, whose
pace the host's turn per dispatch sets."""
from readers import seconds_per_count as read  # noqa: F401
