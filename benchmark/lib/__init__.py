"""The benchmark's own copies of data generators, counter readers and
the plain reference: the part of the yardstick no later PR may change."""
