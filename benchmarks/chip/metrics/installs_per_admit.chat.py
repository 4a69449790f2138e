"""Resident task-stack row installs (``ResidentStack.installs``) per
admission."""
from readers import installs_per_admit as read  # noqa: F401
