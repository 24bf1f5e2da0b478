"""Load generators, one module per loop kind, named by a traffic file's
``generator`` field."""
