"""The chip benchmark of the TCONV serving stack (see README.md)."""
