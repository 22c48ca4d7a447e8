"""The benchmark's yardstick: everything a later PR must not be able to change."""
