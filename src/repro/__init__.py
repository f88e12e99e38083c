"""repro: FlexRound (ICML 2023) as a production-grade JAX PTQ framework."""

__version__ = "1.0.0"
