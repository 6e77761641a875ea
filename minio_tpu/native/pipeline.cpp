// Fused CPU data-plane pipeline: one call per erasure block.
//
// The reference's hot write loop does split -> RS encode (SIMD) -> per-shard
// HighwayHash framing -> disk writes, each stage a separate pass
// (cmd/erasure-encode.go:73-109, cmd/bitrot-streaming.go:74-89). Healthy
// PUT/GET ride the CPU route whatever the device link costs (see
// minio_tpu/erasure/streaming.py), and in Python each stage costs a pass over
// the data plus interpreter overhead per shard. mt_put_block fuses the whole
// block into one GIL-releasing native call, chunk-major so every byte is
// touched while still cache-resident:
//
//   for each bitrot chunk position:
//     copy k data-shard chunks into their framed slots  (split)
//     GF(256)-accumulate m parity chunks into theirs    (encode)
//     HighwayHash all k+m chunks, interleaved x2        (bitrot digests)
//
// mt_get_block is the read-side inverse: verify every chunk digest of the k
// data shards and scatter the payloads into the caller's contiguous block
// (replaces cmd/bitrot-streaming.go:115-151 verify + erasure-utils.go
// writeDataBlocks for the healthy-read path). mt_get_block_pread_degraded
// is the same shape with a rebuild step for reads that miss a data shard:
// verify the k chosen sources, copy the data shards among them and
// GF(256)-accumulate the missing ones, chunk by chunk.
//
// This TU includes the standalone kernels so one libnative.so serves the
// gf256, highwayhash, and pipeline entry points.
#include "gf256_simd.cpp"
#include "highwayhash.cpp"
#include "md5_simd.cpp"
#include "mur3.cpp"

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <fcntl.h>
#include <ftw.h>
#include <string>
#include <sys/stat.h>
#include <unistd.h>

namespace {
inline double mono_s() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}
}  // namespace

namespace {

// bitrot algorithm ids shared with minio_tpu.native (ALGO_* constants)
enum { kAlgoHighway = 0, kAlgoMur3 = 1 };

inline void hash_many(int algo, const uint64_t key[4],
                      const uint8_t* const* hp, const long* hl, int n,
                      uint8_t* digs) {
  if (algo == kAlgoMur3)
    mur3x256_many((const uint8_t*)key, hp, hl, n, digs);
  else
    hh256_many(key, hp, hl, n, digs);
}

// dst[0:len] (^)= c * src[0:len] in GF(256); first=true overwrites
inline void gf_accum(uint8_t c, const uint8_t* src, uint8_t* dst, long len,
                     bool first) {
  long p = 0;
  if (c == 0) {
    if (first) std::memset(dst, 0, (size_t)len);
    return;
  }
  if (c == 1) {
    if (first) {
      std::memcpy(dst, src, (size_t)len);
    } else {
      long q = 0;
#ifdef __AVX2__
      for (; q + 32 <= len; q += 32) {
        __m256i v = _mm256_loadu_si256((const __m256i*)(src + q));
        __m256i a = _mm256_loadu_si256((const __m256i*)(dst + q));
        _mm256_storeu_si256((__m256i*)(dst + q), _mm256_xor_si256(a, v));
      }
#endif
      for (; q < len; q++) dst[q] ^= src[q];
    }
    return;
  }
#ifdef __AVX2__
  const __m256i tlo =
      _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)T.lo[c]));
  const __m256i thi =
      _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)T.hi[c]));
  const __m256i mask = _mm256_set1_epi8(0x0F);
  for (; p + 32 <= len; p += 32) {
    __m256i v = _mm256_loadu_si256((const __m256i*)(src + p));
    __m256i l = _mm256_and_si256(v, mask);
    __m256i h = _mm256_and_si256(_mm256_srli_epi64(v, 4), mask);
    __m256i r = _mm256_xor_si256(_mm256_shuffle_epi8(tlo, l),
                                 _mm256_shuffle_epi8(thi, h));
    if (!first) r = _mm256_xor_si256(
        r, _mm256_loadu_si256((const __m256i*)(dst + p)));
    _mm256_storeu_si256((__m256i*)(dst + p), r);
  }
#endif
  const uint8_t* mrow = T.mul[c];
  if (first)
    for (; p < len; p++) dst[p] = mrow[src[p]];
  else
    for (; p < len; p++) dst[p] ^= mrow[src[p]];
}

}  // namespace

extern "C" {

// Framed shard file size for one block: ceil(shard_len/chunk)*32 + shard_len.
long mt_framed_len(long shard_len, long chunk) {
  if (shard_len <= 0) return 0;
  return ((shard_len + chunk - 1) / chunk) * 32 + shard_len;
}

// One PUT block: split `data` (data_len bytes, zero-padded to k*shard_len)
// into k shards, compute m parity shards (pmat is the [m,k] parity rows),
// and emit k+m bitrot-framed shards ([32B digest][chunk] interleaving,
// chunk size `chunk`) into `out` — (k+m) consecutive spans of
// mt_framed_len(shard_len, chunk) bytes each.
void mt_put_block(const uint8_t* data, long data_len, const uint8_t* pmat,
                  int k, int m, long shard_len, long chunk,
                  const uint64_t key[4], uint8_t* out, int algo) {
  if (k + m > 256 || k <= 0 || m < 0 || chunk <= 0) return;  // hp/hl/hd bound
  const long framed_len = mt_framed_len(shard_len, chunk);
  const long stride = 32 + chunk;  // full-chunk frame stride
  const uint8_t* hp[256];
  long hl[256];
  uint8_t* hd[256];
  long ci = 0;
  for (long c0 = 0; c0 < shard_len; c0 += chunk, ci++) {
    const long clen = (shard_len - c0 < chunk) ? shard_len - c0 : chunk;
    int nh = 0;
    // data shards: copy payloads into framed slots (zero-pad past data end)
    for (int i = 0; i < k; i++) {
      uint8_t* frame = out + (size_t)i * framed_len + ci * stride;
      uint8_t* payload = frame + 32;
      const long spos = (long)i * shard_len + c0;
      long avail = data_len - spos;
      if (avail < 0) avail = 0;
      if (avail > clen) avail = clen;
      if (avail) std::memcpy(payload, data + spos, (size_t)avail);
      if (avail < clen) std::memset(payload + avail, 0, (size_t)(clen - avail));
      hp[nh] = payload;
      hl[nh] = clen;
      hd[nh] = frame;  // digest slot
      nh++;
    }
    // parity shards: GF-accumulate from the k payloads still in cache
    for (int o = 0; o < m; o++) {
      uint8_t* frame = out + (size_t)(k + o) * framed_len + ci * stride;
      uint8_t* payload = frame + 32;
      for (int i = 0; i < k; i++)
        gf_accum(pmat[o * k + i],
                 out + (size_t)i * framed_len + ci * stride + 32, payload,
                 clen, i == 0);
      hp[nh] = payload;
      hl[nh] = clen;
      hd[nh] = frame;
      nh++;
    }
    // digest all k+m chunk payloads (x2-interleaved on AVX2)
    uint8_t digs[256 * 32];
    hash_many(algo, key, hp, hl, nh, digs);
    for (int i = 0; i < nh; i++) std::memcpy(hd[i], digs + i * 32, 32);
  }
}

// mt_put_block + direct shard-file writes in the same GIL-released call:
// after framing into `scratch`, each live shard span is pwrite()n to
// fds[i] at `offset` (pwrite needs no file-position ordering, so blocks
// of one stream can flush out of order from pool workers). fds[i] < 0
// skips shard i (offline disk). errs[i] returns 0 on success, the errno
// on write failure, or -1 on an unexpectedly short write. This replaces
// the per-shard Python write chain (6+ futures per block) with zero
// Python-level writes — the reference leans on per-disk goroutines for
// the same fan-out (cmd/erasure-encode.go:36-54).
// `times`, when non-NULL, returns {encode+hash seconds, pwrite seconds}
// for this call (bench.py's put_stage_breakdown attribution; two
// clock_gettime calls, negligible against a ~0.5 ms block).
void mt_put_block_fds(const uint8_t* data, long data_len, const uint8_t* pmat,
                      int k, int m, long shard_len, long chunk,
                      const uint64_t key[4], uint8_t* scratch, int algo,
                      const int* fds, long offset, int* errs,
                      double* times) {
  if (k + m > 256 || k <= 0 || m < 0 || chunk <= 0) return;
  const double t0 = times ? mono_s() : 0.0;
  mt_put_block(data, data_len, pmat, k, m, shard_len, chunk, key, scratch,
               algo);
  const double t1 = times ? mono_s() : 0.0;
  const long framed_len = mt_framed_len(shard_len, chunk);
  for (int i = 0; i < k + m; i++) {
    errs[i] = 0;
    if (fds[i] < 0) continue;
    const uint8_t* span = scratch + (size_t)i * framed_len;
    long done = 0;
    while (done < framed_len) {
      ssize_t w = pwrite(fds[i], span + done, (size_t)(framed_len - done),
                         offset + done);
      if (w < 0) {
        if (errno == EINTR) continue;
        errs[i] = errno ? errno : -1;
        break;
      }
      if (w == 0) {
        errs[i] = -1;
        break;
      }
      done += w;
    }
  }
  if (times) {
    times[0] = t1 - t0;
    times[1] = mono_s() - t1;
  }
}

// One healthy-read block: `framed` points at k framed data-shard spans (each
// covering `plen` payload bytes chunked at `chunk`); verify every digest and
// scatter payloads into out[i*plen ...]. Returns -1 on success or the index
// of the first shard with a digest mismatch.
int mt_get_block(const uint8_t* const* framed, int k, long plen, long chunk,
                 const uint64_t key[4], uint8_t* out, int algo) {
  if (k <= 0 || k > 256 || chunk <= 0) return -2;  // hp/hl/digs bound
  const long stride = 32 + chunk;
  const uint8_t* hp[256];
  long hl[256];
  uint8_t digs[256 * 32];
  long ci = 0;
  for (long c0 = 0; c0 < plen; c0 += chunk, ci++) {
    const long clen = (plen - c0 < chunk) ? plen - c0 : chunk;
    for (int i = 0; i < k; i++) {
      hp[i] = framed[i] + ci * stride + 32;
      hl[i] = clen;
    }
    hash_many(algo, key, hp, hl, k, digs);
    for (int i = 0; i < k; i++) {
      if (std::memcmp(digs + i * 32, framed[i] + ci * stride, 32) != 0)
        return i;
      std::memcpy(out + (size_t)i * plen + c0, hp[i], (size_t)clen);
    }
  }
  return -1;
}

namespace {
// pread k framed spans (offsets[i] bytes into fds[i]) into k consecutive
// framed_len-byte slots of `scratch`. Returns -1, or -(10+i) when span i's
// read failed or came up short.
inline long pread_spans(const int* fds, const long* offsets, int k,
                        long framed_len, uint8_t* scratch) {
  for (int i = 0; i < k; i++) {
    uint8_t* dst = scratch + (size_t)i * framed_len;
    long done = 0;
    while (done < framed_len) {
      ssize_t r = pread(fds[i], dst + done, (size_t)(framed_len - done),
                        offsets[i] + done);
      if (r < 0) {
        if (errno == EINTR) continue;
        return -(10 + i);
      }
      if (r == 0) return -(10 + i);  // short file
      done += r;
    }
  }
  return -1;
}
}  // namespace

// mt_get_block + the shard-file reads in the same GIL-released call:
// pread each of the k framed spans (offsets[i] bytes into fds[i]) into
// `scratch` (k consecutive spans of mt_framed_len(plen, chunk) bytes),
// then verify+assemble into `out`. Returns -1 on success, the index of
// the first corrupt shard, or -(10+i) when shard i's read failed/came
// up short. Replaces k Python-side reads + buffer handoffs per block
// with zero Python work (the read-side mirror of mt_put_block_fds).
long mt_get_block_pread(const int* fds, const long* offsets, int k,
                        long plen, long chunk, const uint64_t key[4],
                        uint8_t* scratch, uint8_t* out, int algo) {
  if (k <= 0 || k > 256 || chunk <= 0) return -2;
  const long framed_len = mt_framed_len(plen, chunk);
  const long rc = pread_spans(fds, offsets, k, framed_len, scratch);
  if (rc != -1) return rc;
  const uint8_t* ptrs[256];
  for (int i = 0; i < k; i++) ptrs[i] = scratch + (size_t)i * framed_len;
  return mt_get_block(ptrs, k, plen, chunk, key, out, algo);
}

// One degraded-read block in the same shape: pread the k chosen framed
// spans (source j is global shard src_idx[j], ascending), verify every
// source chunk's digest, copy each source that is a data shard to
// out[src_idx[j]*plen ...] and GF(256)-accumulate each missing data shard
// missing[t] = sum_j rows[t*k + j] * source_j straight into
// out[missing[t]*plen ...] — chunk-major, so a chunk is hashed, copied and
// multiplied while still cache-resident. `rows` is the [n_missing, k]
// rebuild matrix over the chosen sources. Returns -1 on success, the
// POSITION in src_idx of the first corrupt source, or -(10+j) when source
// j's read failed/came up short. Replaces the per-block Python read +
// dispatch-queue rebuild of a degraded GET whose sources are local files.
long mt_get_block_pread_degraded(const int* fds, const long* offsets,
                                 const int* src_idx, int k, long plen,
                                 long chunk, const uint64_t key[4],
                                 const uint8_t* rows, const int* missing,
                                 int n_missing, uint8_t* scratch,
                                 uint8_t* out, int algo) {
  if (k <= 0 || k > 256 || chunk <= 0 || n_missing < 0 || n_missing > k)
    return -2;
  const long framed_len = mt_framed_len(plen, chunk);
  const long rc = pread_spans(fds, offsets, k, framed_len, scratch);
  if (rc != -1) return rc;
  const long stride = 32 + chunk;
  const uint8_t* hp[256];
  long hl[256];
  uint8_t digs[256 * 32];
  long ci = 0;
  for (long c0 = 0; c0 < plen; c0 += chunk, ci++) {
    const long clen = (plen - c0 < chunk) ? plen - c0 : chunk;
    for (int j = 0; j < k; j++) {
      hp[j] = scratch + (size_t)j * framed_len + ci * stride + 32;
      hl[j] = clen;
    }
    hash_many(algo, key, hp, hl, k, digs);
    for (int j = 0; j < k; j++)
      if (std::memcmp(digs + j * 32, hp[j] - 32, 32) != 0) return j;
    for (int j = 0; j < k; j++)
      if (src_idx[j] < k)
        std::memcpy(out + (size_t)src_idx[j] * plen + c0, hp[j],
                    (size_t)clen);
    for (int t = 0; t < n_missing; t++) {
      uint8_t* dst = out + (size_t)missing[t] * plen + c0;
      for (int j = 0; j < k; j++)
        gf_accum(rows[t * k + j], hp[j], dst, clen, j == 0);
    }
  }
  return -1;
}

// Verify-only over one framed span (deep scan / VerifyFile): returns -1 ok,
// else the index of the first corrupt chunk.
long mt_verify_framed(const uint8_t* framed, long plen, long chunk,
                      const uint64_t key[4], int algo) {
  const long stride = 32 + chunk;
  uint8_t dig[32];
  long ci = 0;
  for (long c0 = 0; c0 < plen; c0 += chunk, ci++) {
    const long clen = (plen - c0 < chunk) ? plen - c0 : chunk;
    const uint8_t* payload = framed + ci * stride + 32;
    hash_many(algo, key, &payload, &clen, 1, dig);
    if (std::memcmp(dig, framed + ci * stride, 32) != 0) return ci;
  }
  return -1;
}

}  // extern "C"

// --- a request's file-system sequences --------------------------------------
//
// The bytes of a PUT move in mt_put_block_fds; what is left of its drive work
// is file-system choreography: per drive 5 calls to stage a shard file and 21
// to commit a version, each one a turn at the interpreter lock when made from
// Python (~2-3 ms beside 20 clients on the chip's host, PERF.md section 6).
// The entry points below run those sequences with the lock let go once:
// mt_stage_file (storage/xlstorage.py _StagedFile), mt_close_fds (its
// close_many), mt_commit_version (XLStorage.rename_data) and mt_commit_part
// (XLStorage.commit_part: a multipart part's shard and its sidecar). The
// readers' are mt_open_shard (_FileReadAt: open + fstat) and mt_read_file
// (_read_all_inner: open, fstat, read to the end, close: an xl.meta read,
// so a quorum metadata pass is a turn a drive where it was four). They
// perform the steps of the Python sequences in the Python sequences' order,
// fsyncs included; the Python side keeps the policy, the xl.meta logic, the
// errors and the counters (docs/durability.md "The native sequence").
namespace {

// mkdir each directory of `rel` below `base` (never `base` itself): 0 or errno.
// `upto_last` leaves rel's last component alone (it names a file).
int mkdirs_below(const std::string& base, const char* rel, bool upto_last) {
  std::string p = base;
  const char* s = rel;
  while (*s) {
    const char* e = std::strchr(s, '/');
    if (!e) {
      if (upto_last) break;
      e = s + std::strlen(s);
    }
    if (e > s) {
      p.push_back('/');
      p.append(s, (size_t)(e - s));
      if (mkdir(p.c_str(), 0777) != 0 && errno != EEXIST) return errno;
    }
    s = *e ? e + 1 : e;
  }
  return 0;
}

int rm_entry(const char* path, const struct stat*, int, struct FTW*) {
  return remove(path) == 0 ? 0 : -1;
}

// shutil.rmtree of one tree: 0 removed, 1 was not there, -1 failed.
int rm_tree(const char* path) {
  if (nftw(path, rm_entry, 16, FTW_DEPTH | FTW_PHYS) == 0) return 0;
  return errno == ENOENT ? 1 : -1;
}

// durability.fsync_path on a path: a path that cannot be opened is a benign
// race (0, nothing counted); a failed fsync is errno. ok[kind]++ on success.
int fsync_at(const char* path, int flags, int* ok) {
  int fd = open(path, O_RDONLY | O_CLOEXEC | flags);
  if (fd < 0) return 0;
  int e = fsync(fd) == 0 ? 0 : errno;
  close(fd);
  if (!e) ++*ok;
  return e;
}

// steps of mt_commit_version, mt_commit_inline and mt_commit_part, as
// `out[0]` names the one that failed
enum {
  kStepDone = 0,
  // (in brackets: what the step is for a multipart part)
  kStepObjectDir = 1,   // mkdir of the object [upload] directory below the vol
  kStepStaged = 2,      // the staged data directory [part file] is not there
  kStepDataRename = 3,  // <src> -> <object>/<dataDir> [<upload>/part.N]
  kStepMetaWrite = 4,   // xl.meta [the sidecar] written under its tmp name
  kStepMetaRename = 5,  // tmp name -> <object>/xl.meta [<upload>/part.N.meta]
  kStepFsync = 6,       // an fsync of policy `always` failed; out[2] = kind
};

int commit_failed(int* out, int step, int e) {
  out[0] = step;
  out[1] = e;
  return step;
}

// durability.fsync_path(strict) under policy `always`, nothing under another:
// 0, or the errno of a failed fsync with its kind in out[2].
int synced(const char* path, bool dir, int do_fsync, int* out) {
  if (!do_fsync) return 0;
  const int e = fsync_at(path, dir ? O_DIRECTORY : 0, &out[dir ? 4 : 3]);
  if (e) out[2] = dir ? 1 : 0;
  return e;
}

// durable_replace of a new xl.meta (or a part's sidecar): `meta` written to
// `tmp`  [always: fsync it], renamed over `mdst`, a file of <odir>  [always:
// fsync <odir>]. 0, or the step that failed as commit_failed left it in `out`.
int commit_meta(const std::string& mdst, const std::string& odir,
                const std::string& tmp, const uint8_t* meta, long meta_len,
                int do_fsync, int* out) {
  int e;
  int fd = open(tmp.c_str(), O_WRONLY | O_CREAT | O_TRUNC | O_CLOEXEC, 0666);
  if (fd < 0) return commit_failed(out, kStepMetaWrite, errno);
  for (long done = 0; done < meta_len;) {
    ssize_t w = write(fd, meta + done, (size_t)(meta_len - done));
    if (w < 0 && errno == EINTR) continue;
    if (w <= 0) {
      e = w < 0 ? errno : EIO;
      close(fd);
      return commit_failed(out, kStepMetaWrite, e);
    }
    done += w;
  }
  if (do_fsync) {
    if (fsync(fd) != 0) {
      e = errno;
      close(fd);
      out[2] = 0;
      return commit_failed(out, kStepFsync, e);
    }
    out[3]++;
  }
  close(fd);
  if (rename(tmp.c_str(), mdst.c_str()) != 0)
    return commit_failed(out, kStepMetaRename, errno);
  if ((e = synced(odir.c_str(), true, do_fsync, out)))
    return commit_failed(out, kStepFsync, e);
  return kStepDone;
}

// the replaced versions' data directories (n_purge names, NUL-separated)
// removed from <odir>; out[5] counts those that could not be
void purge_ddirs(const std::string& odir, const char* purge, int n_purge,
                 int* out) {
  for (const char* name = purge; n_purge > 0; n_purge--) {
    if (rm_tree((odir + "/" + name).c_str()) < 0) out[5]++;
    name += std::strlen(name) + 1;
  }
}

}  // namespace

extern "C" {

// Stage one shard file: mkdir the directories of `rel` below `base` (the
// volume, which has to be there) and open the file for writing, as
// os.makedirs + open(path, "wb") do. Returns the fd, or -errno.
int mt_stage_file(const char* base, const char* rel) {
  const std::string path = std::string(base) + "/" + rel;
  const int flags = O_WRONLY | O_CREAT | O_TRUNC | O_CLOEXEC;
  // parts after the first find their directory there
  int fd = open(path.c_str(), flags, 0666);
  if (fd >= 0 || errno != ENOENT) return fd >= 0 ? fd : -errno;
  const int e = mkdirs_below(base, rel, true);
  if (e) return -e;
  fd = open(path.c_str(), flags, 0666);
  return fd >= 0 ? fd : -errno;
}

// Open one shard file for reading, as os.open + os.fstat do in
// _FileReadAt: the fd, or -errno (-EISDIR for a directory, which open(2)
// itself lets through).
int mt_open_shard(const char* path) {
  const int fd = open(path, O_RDONLY | O_CLOEXEC);
  if (fd < 0) return -errno;
  struct stat st;
  if (fstat(fd, &st) == 0 && S_ISDIR(st.st_mode)) {
    close(fd);
    return -EISDIR;
  }
  return fd;
}

// Read one whole file, as os.open + os.fstat + os.read to the size fstat
// gave + os.close do in _read_all_inner: the bytes read into `buf`, or
// -errno (-EISDIR for a directory). `*size` is the size fstat gave: a file
// larger than `cap` is not read at all (0 is returned), and the caller asks
// again with a buffer of that size. It writes nothing and fsyncs nothing.
long mt_read_file(const char* path, uint8_t* buf, long cap, long* size) {
  *size = 0;
  const int fd = open(path, O_RDONLY | O_CLOEXEC);
  if (fd < 0) return -errno;
  struct stat st;
  long got = 0;
  if (fstat(fd, &st) != 0) {
    got = -errno;
  } else if (S_ISDIR(st.st_mode)) {
    got = -EISDIR;
  } else if ((*size = (long)st.st_size) <= cap) {
    while (got < *size) {
      const ssize_t r = read(fd, buf + got, (size_t)(*size - got));
      if (r > 0) {
        got += r;
      } else if (r == 0) {
        break;  // the file shrank under the read: what is there
      } else if (errno != EINTR) {
        got = -errno;
        break;
      }
    }
  }
  close(fd);
  return got;
}

// Close n staged files; with do_fsync (policy `always`) each is fsynced
// first. errs[i]: 0, or the errno of the fsync (the file is closed all the
// same). An fd below 0 (a file closed before) is passed over.
void mt_close_fds(const int* fds, int n, int do_fsync, int* errs) {
  for (int i = 0; i < n; i++) {
    errs[i] = 0;
    if (fds[i] < 0) continue;
    if (do_fsync && fsync(fds[i]) != 0) errs[i] = errno ? errno : EIO;
    close(fds[i]);
  }
}

// Commit one version on one drive, the file-system half of rename_data:
//   1. mkdir <vol>/<obj> (every directory of `obj` below the volume)
//   2. [always: fsync <src>]  rename <src> -> <vol>/<obj>/<ddir>, removing a
//      directory found in the way first  [always: fsync <vol>/<obj>]
//   3. write `meta` to <tmp_parent>/xl.meta  [always: fsync it]
//      rename it -> <vol>/<obj>/xl.meta      [always: fsync <vol>/<obj>]
//   4. remove the replaced data directories `purge` (n_purge names,
//      NUL-separated) of <vol>/<obj>, then <tmp_parent>
// Returns 0, or the step that failed with out[1] = errno. out[3], out[4]:
// fsyncs made of kind file / dir; out[5]: data directories that could not be
// removed; out[6]: 1 when <tmp_parent> could not be. Steps 1-3 stop at the
// first failure; step 4 is the clean-up of a commit that stands.
int mt_commit_version(const char* vol, const char* obj, const char* ddir,
                      const char* src, const char* tmp_parent,
                      const uint8_t* meta, long meta_len, const char* purge,
                      int n_purge, int do_fsync, int* out) {
  for (int i = 0; i < 7; i++) out[i] = 0;
  auto fail = [&](int step, int e) { return commit_failed(out, step, e); };
  int e = mkdirs_below(vol, obj, false);
  if (e) return fail(kStepObjectDir, e);
  const std::string odir = std::string(vol) + "/" + obj;
  const std::string dst = odir + "/" + ddir;
  struct stat st;
  if (stat(src, &st) != 0 || !S_ISDIR(st.st_mode))
    return fail(kStepStaged, ENOENT);
  if (stat(dst.c_str(), &st) == 0 && S_ISDIR(st.st_mode) &&
      rm_tree(dst.c_str()) < 0)
    return fail(kStepDataRename, errno);
  if ((e = synced(src, true, do_fsync, out))) return fail(kStepFsync, e);
  if (rename(src, dst.c_str()) != 0) return fail(kStepDataRename, errno);
  if ((e = synced(odir.c_str(), true, do_fsync, out)))
    return fail(kStepFsync, e);

  if (commit_meta(odir + "/xl.meta", odir,
                  std::string(tmp_parent) + "/xl.meta", meta, meta_len,
                  do_fsync, out))
    return out[0];
  purge_ddirs(odir, purge, n_purge, out);
  if (rm_tree(tmp_parent) < 0) out[6] = 1;
  return kStepDone;
}

// Commit one version whose shard rides in `meta` (xl.meta's Data) on one
// drive, the file-system half of rename_data for an inline version: no data
// directory, nothing staged:
//   1. mkdir <vol>/<obj> (every directory of `obj` below the volume)
//   2. write `meta` to `tmp` (a name under .minio.sys/tmp)  [always: fsync it]
//      rename it -> <vol>/<obj>/xl.meta  [always: fsync <vol>/<obj>]
//   3. remove the replaced data directories `purge` of <vol>/<obj>
// `out` as mt_commit_version's (out[6] stays 0).
int mt_commit_inline(const char* vol, const char* obj, const char* tmp,
                     const uint8_t* meta, long meta_len, const char* purge,
                     int n_purge, int do_fsync, int* out) {
  for (int i = 0; i < 7; i++) out[i] = 0;
  const int e = mkdirs_below(vol, obj, false);
  if (e) return commit_failed(out, kStepObjectDir, e);
  const std::string odir = std::string(vol) + "/" + obj;
  if (commit_meta(odir + "/xl.meta", odir, tmp, meta, meta_len, do_fsync, out))
    return out[0];
  purge_ddirs(odir, purge, n_purge, out);
  return kStepDone;
}

// Commit one multipart part on one drive, the file-system half of
// commit_part: the staged shard file <src> becomes <vol>/<part> (`part` is
// <upload>/part.N below the volume) and `meta` its sidecar <vol>/<part>.meta:
//   1. <src> is there; mkdir the directories of `part` below the volume (the
//      upload's: there since the Create, but for a drive that missed it)
//   2. [always: fsync <src>]  rename <src> -> <vol>/<part>  [always: fsync
//      the upload's directory]
//   3. write `meta` to `tmp` (a name under .minio.sys/tmp)  [always: fsync it]
//      rename it -> <vol>/<part>.meta  [always: fsync the upload's directory]
//   4. rmdir <tmp_parent>, the staging directory the shard left empty
// The shard is in place before its sidecar names it, and the sidecar is
// never seen torn. `out` as mt_commit_version's (out[5] stays 0; out[6]: 1
// when <tmp_parent> is there and could not be removed). Steps 1-3 stop at
// the first failure; step 4 is the clean-up of a commit that stands.
int mt_commit_part(const char* vol, const char* part, const char* src,
                   const char* tmp, const char* tmp_parent,
                   const uint8_t* meta, long meta_len, int do_fsync,
                   int* out) {
  for (int i = 0; i < 7; i++) out[i] = 0;
  auto fail = [&](int step, int e) { return commit_failed(out, step, e); };
  struct stat st;
  if (stat(src, &st) != 0) return fail(kStepStaged, ENOENT);
  int e = mkdirs_below(vol, part, true);
  if (e) return fail(kStepObjectDir, e);
  const std::string dst = std::string(vol) + "/" + part;
  const std::string udir = dst.substr(0, dst.rfind('/'));
  if ((e = synced(src, false, do_fsync, out))) return fail(kStepFsync, e);
  if (rename(src, dst.c_str()) != 0) return fail(kStepDataRename, errno);
  if ((e = synced(udir.c_str(), true, do_fsync, out)))
    return fail(kStepFsync, e);
  if (commit_meta(dst + ".meta", udir, tmp, meta, meta_len, do_fsync, out))
    return out[0];
  if (rmdir(tmp_parent) != 0 && errno != ENOENT) out[6] = 1;
  return kStepDone;
}

}  // extern "C"
