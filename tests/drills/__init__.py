"""Acceptance drills the slow-marked tests drive (no ``test_`` prefix: nothing
here is collected).  Each returns a result document; the test asserts on it."""
