"""repro: reproduction of the reconfigurable crossbar training architecture."""
