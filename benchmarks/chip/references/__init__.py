"""Plain float32 references, one module per model family; a configuration
names its family under ``reference``."""
