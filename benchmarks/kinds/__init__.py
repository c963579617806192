"""One module per configuration kind: the seeded generator, the installer,
the query maker and the warm-up enumeration."""
