"""Program adapters, one module per reference: ``programs/<reference>.py``
maps a configuration onto the program's ``ArchConfig`` with a function
``arch_config(config)``, and refuses what its reference cannot check."""
