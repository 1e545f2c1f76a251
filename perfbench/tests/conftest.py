"""Every cell's mix has a tiny version before a test makes a tiny copy."""
import tiny_mixes  # noqa: F401
