"""Share of the traced slice in which no operation ran on the device."""
from readers import idle_share as read  # noqa: F401
